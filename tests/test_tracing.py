"""Device scopes, host spans and counters (``repro.tracing``).

Scopes are read from the ``op_name`` metadata of compiled HLO; host
spans from a CPU profiler trace of one train step and one request.
"""
import gc
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.api import CodedCluster, CodedSession, planner_for_scheme
from repro.configs.registry import get_smoke_config
from repro.models import transformer as tf

_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def hlo_scopes(text: str) -> set:
    """Every name-stack component of the ops' ``op_name`` metadata, with
    transform wrappers (``transpose(jvp(x))``) stripped, that is a
    scope."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]+)"', text):
        for part in op_name.split("/"):
            while (m := _WRAPPED.match(part)):
                part = m.group(1)
            found.add(part)
    return found & set(tracing.SCOPES)


def coded_session(arch: str, **kw) -> CodedSession:
    return CodedSession(
        CodedCluster.homogeneous(2, 2), get_smoke_config(arch),
        planner=planner_for_scheme("hgc", 1, 1), scheme="hgc",
        seq_len=16, total_steps=8, verbose=False, **kw)


def test_scope_names_are_pinned():
    assert tracing.SCOPES == (
        "embed", "attention", "mlp", "moe", "ssm", "ssd", "rglru",
        "head_loss", "weight_cast", "lambda_decode", "codec", "optimizer")
    with pytest.raises(ValueError, match="unknown scope"):
        tracing.scope("attn")


@pytest.mark.parametrize("arch,want", [
    ("mamba2-370m", {"embed", "ssm", "ssd", "head_loss", "weight_cast",
                     "optimizer"}),
    ("starcoder2-3b", {"embed", "attention", "mlp", "head_loss",
                       "weight_cast", "optimizer"}),
    ("granite-moe-3b-a800m", {"embed", "attention", "moe", "head_loss",
                              "weight_cast", "optimizer"}),
    ("recurrentgemma-2b", {"embed", "attention", "rglru", "mlp",
                           "head_loss", "weight_cast", "optimizer"}),
])
def test_train_step_hlo_carries_scopes(arch, want):
    s = coded_session(arch)
    batch = {k: jnp.asarray(v)
             for k, v in s.build_batch((0, 1), [(0, 1), (0, 1)]).items()}
    text = s.train_step.lower(s.params, s.opt_state, batch,
                              jnp.asarray(0)).compile().as_text()
    assert hlo_scopes(text) == want
    # the backward pass keeps the scopes under transpose(...)
    assert re.search(r'op_name="[^"]*transpose\(jvp\([^"]*(embed|ssm|'
                     r'attention)', text)


@pytest.mark.parametrize("weights", ["held", "f32"])
def test_decode_step_hlo_carries_scopes(weights):
    """A serve-only session holds its weights in the compute dtype, so
    its decode casts nothing; float32 weights passed to the same compiled
    decode are cast under ``weight_cast`` on every call."""
    cfg = get_smoke_config("starcoder2-3b")
    s = CodedSession(None, cfg)
    params = (s.params if weights == "held"
              else tf.init_params(jax.random.PRNGKey(0), cfg))
    prefill, decode, _ = s._serve_fns(24, False)
    cache = jax.eval_shape(prefill, params,
                           jax.ShapeDtypeStruct((2, 8), jnp.int32))[1]
    tok = jnp.zeros((2, 1), jnp.int32)
    scopes = hlo_scopes(decode.lower(params, tok, cache).compile().as_text())
    assert {"attention", "embed", "mlp", "head_loss"} <= scopes
    assert ("weight_cast" in scopes) == (weights == "f32")


_DIST = textwrap.dedent("""
    import os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from repro.api import CodedCluster, CodedSession, planner_for_scheme
    from repro.configs.registry import get_smoke_config

    for mode, kw in (("coded", {}), ("coded_q", {"grad_compression": "int8"})):
        s = CodedSession(CodedCluster.homogeneous(2, 2),
                         get_smoke_config("llama3-8b"), mode=mode,
                         planner=planner_for_scheme("hgc", 1, 1),
                         scheme="hgc", seq_len=16, verbose=False, **kw)
        b = s.build_batch((0, 1), [(0, 1), (0, 1)])
        b = {k: jax.device_put(jnp.asarray(v), s._batch_sh[k])
             for k, v in b.items()}
        lam = jax.device_put(jnp.ones((2, 2), jnp.float32), s._lam_sh)
        text = s.train_step.lower(s.params, s.opt_state, b, lam,
                                  s.residual, jnp.asarray(0)
                                  ).compile().as_text()
        names = re.findall(r'op_name="([^"]+)"', text)
        for scope in ("lambda_decode" if mode == "coded" else "codec",
                      "optimizer"):
            hits = [n for n in names if "/" + scope + "/" in n]
            assert hits, (mode, scope)
            print(mode, scope, len(hits))
    print("DIST_SCOPES_OK")
""")


def test_dist_step_hlo_carries_decode_and_optimizer_scopes():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _DIST], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIST_SCOPES_OK" in r.stdout


def _host_events(logdir: str):
    """{name: [(start, end, {stat: value})]} of the ``repro:`` spans."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.setdefault(e.name[len(tracing.PREFIX):], []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def _inside(events, parents) -> bool:
    return all(any(p0 <= a and b <= p1 for p0, p1, _ in parents)
               for a, b, _ in events)


def test_host_spans_nest_under_step_and_request(tmp_path):
    s = coded_session("mamba2-370m")
    s.step()  # compile outside the trace
    server = CodedSession(None, get_smoke_config("starcoder2-3b"))
    prompts = jnp.zeros((2, 8), jnp.int32)
    server.generate(prompts, 3)
    with jax.profiler.trace(str(tmp_path)):
        s.step()
        server.generate(prompts, 3)
        gc.collect()
    ev = _host_events(str(tmp_path))

    steps = ev["train.step"]
    assert len(steps) == 1
    assert {"dropped_edges", "dropped_workers"} <= set(steps[0][2])
    for name in ("pattern", "build_batch", "put", "dispatch", "loss_sync"):
        assert len(ev["train." + name]) == 1, name
        assert _inside(ev["train." + name], steps), name

    req = ev["serve.request"]
    assert len(req) == 1
    assert {"request", "batch", "prompt_len"} <= set(req[0][2])
    assert req[0][2]["batch"] == 2 and req[0][2]["prompt_len"] == 8
    assert len(ev["serve.prefill"]) == 1
    for name in ("decode", "sample", "token_sync"):
        spans = ev["serve." + name]
        assert len(spans) == 3, name
        assert sorted(m["token"] for _, _, m in spans) == [0, 1, 2]
    for name in ("prefill", "decode", "sample", "token_sync"):
        assert _inside(ev["serve." + name], req), name

    assert any(m.get("generation") == 2 for _, _, m in ev["host.gc"])


def test_report_counts_steps_tokens_and_dropped_workers():
    s = coded_session("mamba2-370m")
    report = s.fit(3, force_drop_edge=1, force_drop_step=1)
    c = report["counters"]
    assert set(c) == {"steps", "useful_tokens", "coded_tokens",
                      "dropped_workers"}
    K, D = s.code.K, s.code.load
    assert c["steps"] == 3
    assert c["useful_tokens"] == 3 * K * s.part_batch * s.seq_len
    assert c["coded_tokens"] == 3 * D * 4 * s.part_batch * s.seq_len
    # the forced drop of edge 1 gives both its workers λ = 0
    assert c["dropped_workers"] >= 2
