"""An architecture is found by the configuration's ``model_type``:
its weights, operation counts and reference live in files named after
it, and the generic harness names none.  Qwen2's readings are pinned,
so moving them behind the lookup changed no number."""
import hashlib
import re

import jax
import numpy as np
import pytest

import bench_tiny as T
from bench import common as C
from bench.lm import PARTS, arch, make_params

QWEN2 = "era768_qwen2-7b-l16"
BIG = 2**31 + 12345
# read at the tiny size from the harness before its Qwen2 parts moved
# behind the lookup: the weights' bytes, the operations of three
# requests, and reference logits at three positions of one sequence
WEIGHTS_SHA256 = \
    "a1a8ac24a35a594a91d4b5d49521f517fb50cab6d1614903c5160a27e0c64dfd"
FLOPS = 111719680
LOGITS = {
    "f32": ([[-0.38634318113327026, -0.025305062532424927,
              0.4326457977294922],
             [-1.3123185634613037, -1.76705002784729, 0.39002346992492676],
             [-0.5193943381309509, -0.9416375160217285,
              1.0151371955871582]], -11.893524169921875, 1218.130859375),
    "int8": ([[-0.42002174258232117, 0.013676933944225311,
               0.4560481309890747],
              [-1.3205459117889404, -1.765936255455017,
               0.38792428374290466],
              [-0.5155025124549866, -0.9154372811317444,
               1.0161844491958618]], -12.442754745483398,
             1218.251708984375),
}


@pytest.fixture(scope="module")
def qwen2():
    cfg = T.configs()[QWEN2]
    a = arch(cfg["model_type"])
    return cfg, a, make_params(a.model.weights, cfg, BIG)


def test_lookup_resolves_every_lm_configuration():
    for c in C.benchmark()["configs"]:
        cfg = C.load_json(C.ROOT / c["file"])
        if "model_type" not in cfg:
            continue
        a = arch(cfg["model_type"])
        assert callable(a.model.shape) and callable(a.model.lm_config)
        assert callable(a.model.weights)
        assert callable(a.reference.served_gaps)
        assert callable(a.flops.total_flops)


def test_an_unknown_model_type_names_the_files_to_add():
    with pytest.raises(SystemExit) as e:
        arch("deepseek_v2")
    msg = str(e.value)
    for part in PARTS:
        assert f"bench/{part}/deepseek_v2.py" in msg
    # a type with some of its files names only the missing ones
    with pytest.raises(SystemExit) as e:
        arch("mips_topk")
    assert "bench/models/mips_topk.py" in str(e.value)
    assert "bench/flops/mips_topk.py" not in str(e.value)


def test_an_unknown_model_type_stops_the_cell_at_set_up(tmp_path):
    cfg = dict(T.configs()[QWEN2], model_type="deepseek_v2")
    traffic = T.traffics()["single_doc_rounds"]
    cell = C.kind(traffic["kind"]).Cell(cfg, traffic, 1, tmp_path, {})
    with pytest.raises(SystemExit, match="bench/reference/deepseek_v2.py"):
        cell.setup()


def test_the_generic_harness_names_no_architecture():
    names = [p.stem for p in (C.BENCH / "models").glob("*.py")
             if p.stem != "__init__"]
    assert "qwen2" in names
    files = [C.BENCH / "lm.py", *(C.BENCH / "kinds").glob("*.py"),
             *(C.BENCH / "metrics").glob("*.py")]
    for f in files:
        text = f.read_text().lower()
        for n in names:
            assert not re.search(rf"\b{n}\b", text), (f, n)


def test_qwen2_weights_are_pinned(qwen2):
    _, _, params = qwen2
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


def test_qwen2_flops_are_pinned(qwen2):
    cfg, a, _ = qwen2
    shp = a.model.shape(cfg)
    assert a.flops.total_flops(shp, [(37, 5), (300, 96), (1, 1)]) == FLOPS


@pytest.mark.parametrize("mode", ["f32", "int8"])
def test_qwen2_reference_logits_are_pinned(qwen2, mode):
    cfg, a, params = qwen2
    ids = [(7 * i + 3) % 512 for i in range(300)]
    lg = a.reference.logits(params, a.model.shape(cfg), ids, [0, 150, 299],
                            mode)
    head, total, mass = LOGITS[mode]
    # float32 sums over 300 positions: equal up to the last bits that
    # the CPU's vector width may order differently
    np.testing.assert_allclose(lg[:, :3], head, rtol=1e-6, atol=1e-6)
    assert float(lg.sum()) == pytest.approx(total, rel=1e-5, abs=1e-4)
    assert float(np.abs(lg).sum()) == pytest.approx(mass, rel=1e-6)
