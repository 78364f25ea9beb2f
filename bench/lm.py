"""The configuration's LM: its architecture's modules, found by the
configuration's ``model_type``; weights made on the device from the
seed, in the type they are served in; and a recorder of what the
program's engine served.

An architecture is three files named after its ``model_type``, and
nothing else of the harness names it:

- ``models/<model_type>.py``: ``shape(cfg)``, the widths the other two
  read; ``lm_config(cfg)``, the program's ``LMConfig``; and
  ``weights(cfg, key)``, every weight in the layout the program's
  transformer reads, traced inside one jitted call;
- ``reference/<model_type>.py``: ``served_gaps(params, shape, prompt,
  served, control)``, the plain float32 forward's verdict on served
  tokens, and the control one precision step below the configuration's;
- ``flops/<model_type>.py``: ``total_flops(shape, requests)``.

The weights are the benchmark's, not the program's, so the reference
and the program start from the same numbers and nothing of the
program's own initialisation.
"""
from __future__ import annotations

import functools
import importlib
from typing import Dict, List, NamedTuple, Tuple

import jax

from bench.common import BENCH, ROOT

PARTS = ("models", "reference", "flops")


class Arch(NamedTuple):
    model: object
    reference: object
    flops: object


def arch(model_type: str) -> Arch:
    """The modules of ``model_type``; a type without all three stops
    the run and names the files to add."""
    paths = [BENCH / part / f"{model_type}.py" for part in PARTS]
    missing = [str(p.relative_to(ROOT)) for p in paths if not p.is_file()]
    if missing:
        raise SystemExit(
            f"no benchmark modules for model_type {model_type!r}: add "
            f"{', '.join(missing)} (see bench/lm.py)")
    return Arch(*(importlib.import_module(f"bench.{part}.{model_type}")
                  for part in PARTS))


def make_params(weights, cfg: dict, seed: int):
    """Every weight from ``seed`` in one jitted program; ``weights`` is
    the architecture's ``weights(cfg, key)``."""
    # a seed of more than 32 bits is folded in word by word
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(functools.partial(weights, cfg))(key)


class _Sink(dict):
    """The engine's result dict, which also keeps a copy of every
    finished request's tokens for the check."""

    def __init__(self, sink: Dict[int, List[int]]):
        super().__init__()
        self.sink = sink

    def __setitem__(self, k, v):
        self.sink[k] = [int(t) for t in v]
        super().__setitem__(k, v)


class Recorder:
    """What the engine served: each request's prompt, budget and
    tokens, kept from the engine's own entry and result points."""

    def __init__(self, engine):
        self.prompts: Dict[int, Tuple[str, int]] = {}
        self.outputs: Dict[int, List[int]] = {}
        submit = engine.submit

        def recording_submit(prompt, max_new_tokens=None, prefix=None):
            rid = submit(prompt, max_new_tokens, prefix=prefix)
            budget = engine.ecfg.max_new_tokens \
                if max_new_tokens is None else max_new_tokens
            self.prompts[rid] = (prompt, int(budget))
            return rid

        engine.submit = recording_submit
        engine._results = _Sink(self.outputs)

    def clear(self) -> None:
        self.prompts.clear()
        self.outputs.clear()

    def finished(self) -> List[Tuple[int, str, int, List[int]]]:
        """(request id, prompt, budget, served tokens), in order."""
        return [(rid, self.prompts[rid][0], self.prompts[rid][1],
                 self.outputs[rid]) for rid in sorted(self.outputs)
                if rid in self.prompts]
