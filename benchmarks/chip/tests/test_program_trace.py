"""The program's spans and scopes in a trace (``chipbench/program.py``),
on hand-made traces and on recorded TPU slices.

  python -m pytest benchmarks/chip/tests/test_program_trace.py -q
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import program as pg  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.program import ProgramTrace, ScopedOp  # noqa: E402

STEP = "jit(train_step)/"
SHMAP = STEP + "shard_map/"


@pytest.mark.parametrize("op_name,want", [
    (STEP + "while/body/closed_call/checkpoint/ssm/ssd/dot_general",
     ("ssd", False)),
    ("checkpoint/ssm/reduce_sum", ("ssm", False)),
    (STEP + "jvp(head_loss)/log", ("head_loss", False)),
    (STEP + "jvp(weight_cast)/convert_element_type", ("weight_cast", False)),
    (STEP + "transpose(jvp(embed))/scatter-add", ("embed", True)),
    (STEP + "transpose(jvp())/while/body/closed_call/checkpoint/ssm/ssd/"
     "while/body/closed_call/add_any", ("ssd", True)),
    # remat's recompute runs inside the backward pass
    (STEP + "transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/ssm/ssd/bchqk,bckhd->bcqhd/dot_general",
     ("ssd", True)),
    (SHMAP + "lambda_decode/psum", ("lambda_decode", False)),
    (STEP + "optimizer/jit(clip_by_global_norm)/mul", ("optimizer", False)),
    (STEP + "while/body/add", ("", False)),
    ("", ("", False)),
])
def test_scope_of(op_name, want):
    assert pg.scope_of(op_name) == want


def test_self_times_subtract_nested_ops():
    ops = [ScopedOp("while.1", 0, 10, "while"),
           ScopedOp("fusion.1", 1, 4, "fusion"),
           ScopedOp("fusion.2", 5, 6, "fusion"),
           ScopedOp("copy.1", 11, 12, "copy")]
    assert pg.self_times(ops) == [6, 3, 1, 1]


def step_trace() -> ProgramTrace:
    """Two train steps of 10 s on two chips; chip 1 runs the same ops
    shifted by 0.5 s.  Per step: 2 s forward (ssd 1), 3 s backward (ssd
    2, half of it remat recompute), 1 s λ-decode, 1 s optimizer, 1 s
    unscoped; 2 s idle in which the host builds the batch."""
    fwd = STEP + "while/body/checkpoint/ssm/"
    bwd = STEP + "transpose(jvp())/while/body/checkpoint/"

    def one(t0):
        return [
            ScopedOp("fusion.1", t0 + 2, t0 + 3, "fusion", fwd + "mul"),
            ScopedOp("fusion.2", t0 + 3, t0 + 4, "fusion", fwd + "ssd/mul"),
            ScopedOp("fusion.3", t0 + 4, t0 + 5, "fusion",
                     bwd + "rematted_computation/ssm/ssd/mul"),
            ScopedOp("fusion.4", t0 + 5, t0 + 6, "fusion",
                     bwd + "ssm/ssd/add_any"),
            ScopedOp("fusion.5", t0 + 6, t0 + 7, "fusion", bwd + "ssm/mul"),
            ScopedOp("psum.1", t0 + 7, t0 + 8, "all-reduce",
                     SHMAP + "lambda_decode/psum"),
            ScopedOp("fusion.6", t0 + 8, t0 + 9, "fusion",
                     STEP + "optimizer/add"),
            ScopedOp("copy.1", t0 + 9, t0 + 10, "copy", STEP + "copy"),
        ]

    d0 = one(0) + one(10)
    d1 = [ScopedOp(o.name, o.start + 0.5, o.end + 0.5, o.kind, o.op_name)
          for o in d0]
    spans = {
        "train.step": [(0, 10, {"step_num": 0}), (10, 20, {"step_num": 1})],
        "train.pattern": [(0, 0.5, {}), (10, 10.5, {})],
        "train.build_batch": [(0.5, 1.5, {}), (10.5, 11.5, {})],
        "train.put": [(1.5, 1.8, {}), (11.5, 11.8, {})],
        "train.dispatch": [(1.8, 2.0, {}), (11.8, 12.0, {})],
        "train.loss_sync": [(2.0, 10, {}), (12.0, 20, {})],
    }
    return ProgramTrace({0: d0, 1: d1}, spans, (0, 20))


def test_layer_ms_per_step():
    t = step_trace()
    # chip 1's last op is cut at the window's end: 0.5 s less unscoped
    assert pg.layer_ms(t, "train.step", backward=False) == \
        pytest.approx(2e3)
    assert pg.layer_ms(t, "train.step", backward=True) == \
        pytest.approx(3e3)
    assert pg.layer_ms(t, "train.step", ("ssd",)) == pytest.approx(3e3)
    assert pg.layer_ms(t, "train.step", ("optimizer",)) == \
        pytest.approx(1e3)
    assert pg.layer_ms(t, "train.step", ("lambda_decode",)) == \
        pytest.approx(1e3)
    secs = pg.scope_seconds(t)
    assert secs["unscoped"] == pytest.approx(2 - 0.25)
    assert secs["ssd.bwd"] == pytest.approx(4) and secs["ssm.fwd"] == 2
    assert pg.span_ms(t, pg.TRAIN_HOST, "train.step") == \
        pytest.approx(2e3)
    assert [label for label, *_ in pg.top_unscoped(t)] == ["copy:copy"]


def test_idle_gaps_named_by_innermost_program_span():
    gaps = pg.idle_gaps(step_trace())
    # 0..2 s: the middle (1 s) lies in build_batch inside train.step
    assert gaps[0] == ["repro:train.build_batch", pytest.approx(2)]
    assert gaps[1] == ["repro:train.build_batch", pytest.approx(2)]


def test_program_without_instrumentation_reads_nothing():
    t = step_trace()
    bare = ProgramTrace(
        {d: [ScopedOp(o.name, o.start, o.end, o.kind) for o in ops]
         for d, ops in t.devices.items()}, {}, t.window)
    assert pg.layer_ms(bare, "train.step", backward=False) is None
    # spans alone do not give a scope
    assert pg.layer_ms(ProgramTrace(bare.devices, t.spans, t.window),
                       "train.step") is None
    assert pg.span_ms(bare, pg.TRAIN_HOST, "train.step") is None
    assert pg.idle_gaps(bare)[0][0] == "host:untraced"


def test_json_round_trip(tmp_path):
    t = step_trace()
    pg.save(t, tmp_path / "t.json")
    u = pg.load(tmp_path / "t.json")
    assert u.window == pytest.approx(t.window)
    for d, ops in t.devices.items():
        assert [(o.name, o.kind, o.op_name) for o in u.devices[d]] == \
            [(o.name, o.kind, o.op_name) for o in ops]
        assert [o.start for o in u.devices[d]] == \
            pytest.approx([o.start for o in ops], abs=1e-9)
    assert u.spans["train.step"][1][2] == {"step_num": 1}


# ----------------------------------------------------------------------
# the module HLO in the trace, read from the protobuf wire format
# ----------------------------------------------------------------------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_module_op_names_from_the_metadata_plane(tmp_path):
    instr = [_msg((1, "fusion.7"), (2, "fusion"),
                  (7, _msg((1, "mul"), (2, "jit(f)/ssd/mul")))),
             _msg((1, "param.1"), (2, "parameter"))]
    module = _msg((1, "jit_f"), (3, _msg((1, "main"), *[(2, i)
                                                       for i in instr])))
    hlo_proto = _msg((1, module))
    stat_meta = _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))
    event_meta = _msg((1, 42), (2, _msg(
        (1, 42), (2, "jit_f(42)"), (5, _msg((1, 7), (6, hlo_proto))))))
    plane = _msg((1, 1), (2, "/host:metadata"), (4, event_meta),
                 (5, stat_meta))
    other = _msg((1, 2), (2, "/host:CPU"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, other), (1, plane)))
    assert pg.module_op_names(str(path)) == {
        "jit_f(42)": {"fusion.7": "jit(f)/ssd/mul"}}


# ----------------------------------------------------------------------
# recorded slices of the cells on a TPU v5e (``layers.py --slice``)
# ----------------------------------------------------------------------
def test_recorded_decode_call():
    """30 ms of the serve cell from the end of one ``serve.decode``
    dispatch: a decode program with its weight cast, attention kernel,
    MLP and head, each op joined to its scope."""
    t = pg.load(HERE / "data" / "serve_decode_program.json")
    ops = t.ops(0)
    assert t.window[1] - t.window[0] == pytest.approx(0.030)
    # every convert is the weight recast, and nothing else is
    converts = [o for o in ops if o.kind == "convert"]
    assert converts and {pg.scope_of(o.op_name) for o in converts} == {
        ("weight_cast", False)}
    secs = pg.scope_seconds(t)
    assert 0.015 < secs["weight_cast.fwd"] < 0.020
    assert {"attention.fwd", "mlp.fwd", "head_loss.fwd",
            "embed.fwd"} <= set(secs)
    assert not any(k.endswith(".bwd") for k in secs)
    # the kernel keeps the name decode_attn_roofline matches
    kernels = [o for o in ops if 'target="tpu_custom_call"' in o.name]
    assert kernels and all(
        o.name.startswith("%decode_attention_fwd")
        and pg.scope_of(o.op_name)[0] == "attention" for o in kernels)
    # the device waits while the host waits for the token
    gaps = pg.idle_gaps(t)
    assert gaps[0][0] == "repro:serve.token_sync" and gaps[0][1] > 1e-3
    assert all(name.startswith(pg.SPAN_PREFIX) for name, _ in gaps)
    assert t.spans["serve.decode"][0][2] == {"token": 41}


def test_recorded_step_end():
    """116 ms of the train cell on a TPU v5e 2x2 (chip 0): the tail of
    one step's backward pass, its λ-decode and optimizer, and the host
    work before the next step's program starts."""
    t = pg.load(HERE / "data" / "train_step_program.json")
    ops = t.ops(0)
    secs = pg.scope_seconds(t)
    # the whole λ-decode of the step runs here, exposed: nothing beside
    # the psums, so the harness's exposed-collective time agrees
    assert 0.070 < secs["lambda_decode.fwd"] < 0.080
    assert tr.exposed_collective_s(t.harness_trace(), 0) == \
        pytest.approx(secs["lambda_decode.fwd"], rel=0.01)
    assert 0.008 < secs["optimizer.fwd"] < 0.012
    # backward ops, remat's recompute among them, carry their scopes
    assert {"ssd.bwd", "ssm.bwd", "embed.bwd"} <= set(secs)
    assert any("rematted_computation" in o.op_name
               and pg.scope_of(o.op_name) == ("ssd", True) for o in ops)
    # the device idles while the host puts and dispatches the next step
    gaps = pg.idle_gaps(t)
    assert all(name.startswith(pg.SPAN_PREFIX) for name, _ in gaps)
    assert {name for name, g in gaps if g > 1e-3} == {
        "repro:train.dispatch", "repro:train.loss_sync", "repro:train.put"}
    # pattern 0.5, batch 1.5, put 8.8 and dispatch 7.2 ms, per step
    assert pg.span_ms(t, pg.TRAIN_HOST, "train.step") == \
        pytest.approx(18.06, abs=0.01)
    step = t.spans["train.step"][-1][2]
    assert step["dropped_edges"] == 1 and step["dropped_workers"] == 3
