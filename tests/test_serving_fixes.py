"""Serving-path bugfix regressions.

Three defects found in the serving sweep, each locked down by a test
that fails on the pre-fix code:

- ``generate_batch`` detokenized the terminal EOS sentinel into the
  answer text (``... tok2``);
- ``submit(prompt, max_new_tokens=0)`` silently fell back to the
  engine default budget via ``or`` truthiness instead of rejecting a
  nonsensical explicit budget;
- the adaptive-search merge sorted on score alone, so ties between the
  leaf and summary scans kept concatenation order — the budgeted
  context depended on which layer was scanned first.
"""
import numpy as np
import pytest

from repro.core.retrieve import adaptive_search_batch
from repro.core.store import Hit
from repro.data.tokenizer import EOS_ID
from repro.obs import NULL_TRACER


# ----------------------------------------------------------------------
# EOS sentinel must not leak into detokenized answers
# ----------------------------------------------------------------------

def _stub_results(eng, toks):
    """Route every queued request to a fixed token list (bypasses the
    LM so the terminal-token handling is tested in isolation)."""
    def fake(max_iters=10_000):
        while not eng._queue.empty():
            rid, *_ = eng._queue.get()
            eng._results[rid] = list(toks)
    eng.run_until_done = fake


@pytest.mark.serving
def test_terminal_eos_stripped(engine_fixture):
    eng = engine_fixture()
    _stub_results(eng, [7, 9, EOS_ID])
    assert eng.generate_batch(["x"]) == ["tok7 tok9"]


@pytest.mark.serving
def test_eos_only_answer_is_empty(engine_fixture):
    eng = engine_fixture()
    _stub_results(eng, [EOS_ID])
    assert eng.generate_batch(["x"]) == [""]


@pytest.mark.serving
def test_budget_terminated_answer_untouched(engine_fixture):
    # no terminal EOS (budget exhaustion): nothing is stripped, even
    # when an EOS id appears mid-sequence
    eng = engine_fixture()
    _stub_results(eng, [7, EOS_ID, 9])
    assert eng.generate_batch(["x"]) == ["tok7 tok2 tok9"]


# ----------------------------------------------------------------------
# explicit zero/negative decode budgets are caller bugs, not defaults
# ----------------------------------------------------------------------

@pytest.mark.serving
def test_zero_budget_raises(engine_fixture):
    eng = engine_fixture()
    with pytest.raises(ValueError):
        eng.submit("a question", max_new_tokens=0)
    with pytest.raises(ValueError):
        eng.submit("a question", max_new_tokens=-3)
    with pytest.raises(ValueError):
        eng.generate_batch(["a question"], max_new_tokens=0)


@pytest.mark.serving
def test_none_budget_uses_engine_default(engine_fixture):
    eng = engine_fixture(max_new_tokens=3)
    out = eng.generate("a question", max_new_tokens=None)
    assert 1 <= len(out.split()) <= 3


# ----------------------------------------------------------------------
# adaptive merge: score ties break on insertion seq, not scan order
# ----------------------------------------------------------------------

class _Node:
    def __init__(self, text):
        self.text = text
        self.n_tokens = len(text.split())


class _TieGraph:
    nodes = {"a": _Node("alpha fact"), "b": _Node("bravo fact")}


class _TieStore:
    """Leaf scan yields node ``a`` (seq 5), summary scan node ``b``
    (seq 2), with identical scores — the merged order must be seq
    order regardless of which scan ran first."""
    epoch = 0
    tracer = NULL_TRACER

    def search_batch(self, q, k, layer_filter=None):
        if layer_filter == "leaf":
            return [[Hit("a", 1.0, 0, seq=5)]]
        return [[Hit("b", 1.0, 1, seq=2)]]


def test_adaptive_tie_breaks_on_seq():
    q = np.zeros((1, 4), np.float32)
    for mode in ("detailed", "summarized"):
        [r] = adaptive_search_batch(_TieGraph(), _TieStore(), q, k=2,
                                    token_budget=100, p=0.5, mode=mode)
        assert [h.node_id for h in r.hits] == ["b", "a"], mode


def test_adaptive_tie_order_sets_budgeted_context():
    # with budget for ONE hit the tie-break decides the whole context:
    # both scan orders must agree on the lower-seq node
    q = np.zeros((1, 4), np.float32)
    ctxs = set()
    for mode in ("detailed", "summarized"):
        [r] = adaptive_search_batch(_TieGraph(), _TieStore(), q, k=2,
                                    token_budget=2, p=0.5, mode=mode)
        ctxs.add(r.context)
    assert ctxs == {"bravo fact"}
