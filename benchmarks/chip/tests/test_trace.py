"""The trace reduction on hand-made traces and on a small recorded one.

  python -m pytest benchmarks/chip/tests/test_trace.py -q
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import trace as tr  # noqa: E402
from chipbench.trace import Op, Trace  # noqa: E402


def test_union_subtract_length():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.length([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 1)], []) == [(0, 1)]
    assert tr.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]


def two_chip_trace():
    # window 0..10 s; chip 0: compute 0-4, all-reduce 3-6 (2 s exposed),
    # async all-gather start 7 / done 8.5 with compute 7.5-8 (1 s exposed)
    d0 = [Op("fusion.1", 0, 4, "fusion"), Op("psum.3", 3, 6, "all-reduce"),
          Op("all-gather-start.2", 7, 7.1, "all-gather-start"),
          Op("fusion.2", 7.5, 8, "fusion"),
          Op("all-gather-done.2", 8.4, 8.5, "all-gather-done")]
    # chip 1: one op straddling the window's end
    d1 = [Op("fusion.1", 9, 12, "fusion")]
    host = {"window": [(0, 10)], "step": [(0, 5), (5, 9.5)],
            "build_batch": [(6.2, 6.9)]}
    return Trace({0: d0, 1: d1}, host, (0, 10))


def test_busy_and_idle():
    t = two_chip_trace()
    # busy is where ops run: an async pair's start and done events
    # count, the transfer between them does not
    assert tr.busy_s(t, 0) == pytest.approx(6 + 0.1 + 0.5 + 0.1)
    assert tr.busy_s(t, 1) == pytest.approx(1.0)   # clipped at 10
    assert tr.mean_busy_s(t) == pytest.approx((6.7 + 1.0) / 2)
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["host:step", pytest.approx(1.5)]   # 8.5..10
    assert ["host:build_batch", pytest.approx(1.0)] in gaps  # 6..7


def test_exposed_collectives():
    t = two_chip_trace()
    coll = tr.collective_intervals(t.ops(0))
    assert coll == [(3, 6), (7, 8.5)]
    assert tr.exposed_collective_s(t, 0) == pytest.approx(2 + 1)
    assert tr.exposed_collective_s(t, 1) == 0


def test_op_seconds_and_top_ops():
    t = two_chip_trace()
    secs, n = tr.op_seconds(t, r"^fusion")
    assert n == 1 and secs == pytest.approx((4 + 0.5 + 1) / 2)
    top = dict(tr.top_ops(t))
    assert top["fusion:fusion"] == pytest.approx((4.5 + 1) / 2)
    assert top["psum:all-reduce"] == pytest.approx(1.5)


def test_hlo_names_and_self_time():
    name, kind = tr.parse_hlo(
        "%psum.249 = f32[48,2048,1024]{2,1,0:T(8,128)} all-reduce("
        "f32[48,2048,1024]{2,1,0:T(8,128)} %psum.248), channel_id=1")
    assert (name, kind) == ("psum.249", "all-reduce")
    assert tr.parse_hlo("%while.1 = (s32[]{:T(128)}, bf16[4]{0}) while("
                        "%t)")[1] == "while"
    # a loop's event encloses its body's ops: self time excludes them
    ops = [Op("while.1", 0, 10, "while"), Op("fusion.1", 1, 4, "fusion"),
           Op("fusion.2", 5, 6, "fusion"), Op("copy.1", 11, 12, "copy")]
    assert tr.self_seconds(ops) == {"while:while": 6, "fusion:fusion": 4,
                                    "copy:copy": 1}
    # an op inside a loop is still compute for the exposed-collective
    # reduction, the loop's own event is not
    t = Trace({0: [Op("while.1", 0, 10, "while"),
                   Op("psum.1", 2, 3, "all-reduce"),
                   Op("fusion.1", 2.5, 4, "fusion")]},
              {"window": [(0, 10)]}, (0, 10))
    assert tr.exposed_collective_s(t, 0) == pytest.approx(0.5)


def test_json_round_trip(tmp_path):
    t = two_chip_trace()
    tr.save(t, tmp_path / "t.json")
    u = tr.load(tmp_path / "t.json")
    assert u.window == t.window and u.devices[0] == t.devices[0]


def grid_exposed(t: Trace, dev: int, res: float = 1e-6) -> float:
    """The exposed-collective time, sampled on a 1 us grid."""
    import numpy as np

    lo, hi = t.window
    grid = np.arange(lo, hi, res)

    def covered(ops):
        mask = np.zeros(grid.shape, bool)
        for a, b in ops:
            mask[np.searchsorted(grid, a):np.searchsorted(grid, b)] = True
        return mask

    ops = t.ops(dev)
    coll = covered(tr.collective_intervals(ops))
    comp = covered((o.start, o.end) for o in ops if not tr.is_collective(o)
                   and o.kind not in tr.CONTAINERS)
    return float(np.sum(coll & ~comp)) * res


def test_recorded_step_end():
    """80 ms at the end of a coded train step on a TPU v5e 2x2 (chips 0
    and 1): the λ-decode psums of the whole gradient run back to back."""
    t = tr.load(HERE / "data" / "train_step_end.json")
    assert sorted(t.devices) == [0, 1] and t.window_s == pytest.approx(0.08)
    kinds = {o.kind for o in t.ops(0)}
    assert {"all-reduce", "fusion", "while"} <= kinds
    for d in t.devices:
        assert 0 < tr.busy_s(t, d) <= t.window_s
        exposed = tr.exposed_collective_s(t, d)
        assert exposed == pytest.approx(grid_exposed(t, d), abs=2e-5)
        assert 0.04 < exposed < t.window_s
    assert tr.top_ops(t, 1)[0][0] == "psum:all-reduce"


def test_recorded_decode_roofline():
    """60 ms of the serve cell's decode on a TPU v5e: the fused decode
    kernel's events are found by name and its roofline share read."""
    import types

    t = tr.load(HERE / "data" / "serve_decode_steps.json")
    secs, calls = tr.op_seconds(t, r"^%?decode_attention_fwd")
    assert calls == 49 and 20e-6 < secs / calls < 30e-6
    reader = __import__("chipbench.spec", fromlist=["x"]).load_module(
        HERE.parent / "metrics" / "decode_attn_roofline.py", "roofline")
    run = types.SimpleNamespace(
        trace=t, peaks={"hbm_bytes_per_s": 819e9},
        record=types.SimpleNamespace(values={
            "batch": 4, "cache_len": 1153, "q_itemsize": 2,
            "cache_itemsize": 4}),
        cell=types.SimpleNamespace(config={"model": {
            "n_heads": 24, "n_kv_heads": 2, "head_dim": 128}}))
    nbytes = 2 * 4 * 24 * 128 * 2 + 2 * 4 * 1153 * 2 * 128 * 4
    want = 100 * nbytes / 819e9 / (secs / calls)
    assert reader.read(run) == pytest.approx(want)
    assert 40 < want < 50
