"""The chip smoke script's CPU rehearsal: every phase of the one-chip
path (build, LM insert, checked queries, answers) at a tiny size, so
the script cannot rot between chip runs."""
import importlib.util
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_runs_every_phase(capsys):
    assert _load().main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    for phase in ("build", "lm_init", "lm_insert", "query_exact",
                  "query_quantized", "query_multihop", "answer"):
        assert f"phase {phase}:" in out
    assert "token-identical over two runs" in out
    assert '"ok"' not in out  # a rehearsal never claims a chip run


def test_refuses_a_backend_without_tpu(capsys):
    assert _load().main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.multidevice
def test_four_chip_rehearsal_matches_flat_store(capsys):
    assert _load().main(["--rehearse", "--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert "phase sharded_query:" in out
    assert out.count("one collective launch") == 4
