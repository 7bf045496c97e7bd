"""Stage scopes in the chunk program, its stable name, and the registry's
set-up counters.

  * the batched chunk program of both Handel modes, compiled on the CPU
    through `CompileRegistry`, is the module ``jit_chunk_batched`` and
    carries the engine's four stage scopes and Handel's four sub-scopes
    in its ops' ``op_name`` metadata (a device trace reduces by them);
  * every engine and plane gets its own stable program name;
  * the process-wide compile counters rise on a cold compile, report a
    persistent-cache hit on a warm one, and reach ``/w/batch/metrics``.
"""

import json
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import wittgenstein_tpu.models  # noqa: F401 — fills the registry
from wittgenstein_tpu.obs.metrics import parse_exposition
from wittgenstein_tpu.serve import ScenarioSpec, Scheduler
from wittgenstein_tpu.serve.instrument import (COMPILE_COUNTERS,
                                               Instrumentation,
                                               scheduler_exposition)
from wittgenstein_tpu.serve.registry import CompileRegistry

REPO = Path(__file__).resolve().parent.parent
STAGES = ("engine.inbox", "protocol.step", "engine.route", "engine.bin")
SUBS = ("handel.receive", "handel.apply", "handel.verify",
        "handel.disseminate")


def _handel_spec(config):
    body = json.loads((REPO / "benchmark" / "configs" /
                       f"{config}.json").read_text())["spec"]
    body["params"].update(node_count=64, nodes_down=6, threshold=57)
    body.update(seeds=[0, 1], chunk_ms=20, sim_ms=40)
    return ScenarioSpec.from_json(body).validate()


def _scopes(op_name):
    """The path's components, transform wrappers (``vmap(...)``)
    taken off."""
    return [re.sub(r"^\w+\((.*)\)$", r"\1", c) for c in op_name.split("/")]


@pytest.mark.parametrize("config", ["handel-2048-exact",
                                    "handel-cardinal-65536"])
def test_chunk_program_carries_every_stage_scope(config):
    spec = _handel_spec(config)
    proto = spec.build_protocol()
    chunk = CompileRegistry(persistent=False).chunk_fn(spec, None,
                                                       proto=proto)
    nets, ps = jax.jit(jax.vmap(proto.init))(jnp.arange(2, dtype=jnp.int32))
    text = chunk.__wrapped__.lower(nets, ps).compile().as_text()
    assert re.search(r"^HloModule jit_chunk_batched\b", text, re.M)
    paths = [_scopes(m) for m in re.findall(r'op_name="([^"]+)"', text)]
    for stage in STAGES:
        assert any(stage in p for p in paths), stage
    for sub in SUBS:      # sub-scopes sit under the protocol stage only
        under = [p for p in paths if sub in p]
        assert under, sub
        assert all("protocol.step" in p and
                   p.index("protocol.step") < p.index(sub) for p in under)


@pytest.mark.parametrize("engine,plane,name", [
    ("batched", None, "chunk_batched"),
    ("batched", "metrics", "chunk_batched_metrics"),
    ("fast_forward", None, "chunk_fast_forward"),
    ("vmapped", "audit", "chunk_vmapped_audit"),
])
def test_chunk_program_names_are_stable(engine, plane, name):
    if engine == "batched":
        spec = _handel_spec("handel-2048-exact")
    else:
        spec = ScenarioSpec(protocol="PingPong", params={"node_count": 64},
                            seeds=(0,), sim_ms=80, chunk_ms=40,
                            engine=engine).validate()
    chunk = CompileRegistry(persistent=False).chunk_fn(spec, plane)
    assert chunk.__wrapped__.__name__ == name


#: Run in a process of its own: it points the persistent compile cache
#: at a fresh directory, which is process-wide.  Each call of `fresh`
#: is a new function, so JAX's in-memory caches miss and the second
#: compile is a load from the persistent cache.
COUNTERS_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from wittgenstein_tpu.serve.registry import CompileRegistry

def fresh():
    f = jax.jit(lambda x: jnp.cumsum(jnp.sort(x) * 3.0) - 3.0)
    jax.block_until_ready(f(jnp.arange(97.0)))

reg = CompileRegistry(persistent=False)
out = [reg.compile_stats()]
fresh()
out.append(reg.compile_stats())
fresh()
out.append(reg.compile_stats())
out.append([sorted(reg.registry_block()), sorted(reg.stats())])
print(json.dumps(out))
"""


def test_registry_counts_cold_compiles_and_cache_hits(tmp_path):
    p = subprocess.run([sys.executable, "-c", COUNTERS_SCRIPT,
                        str(tmp_path)], cwd=REPO, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    s0, cold, warm, (block, stats) = json.loads(p.stdout.splitlines()[-1])
    assert s0["trace_s"] == s0["compile_s"] == 0
    assert cold["trace_s"] > 0 and cold["compile_s"] > 0
    assert cold["cache_misses"] >= 1 and cold["cache_hits"] == 0
    assert warm["cache_hits"] >= 1
    assert warm["cache_misses"] == cold["cache_misses"]
    assert warm["compile_s"] > cold["compile_s"]   # the load
    assert set(warm) <= set(block)
    assert not set(warm) & set(stats)   # artifacts: no walls


def test_nested_trace_spans_count_once(monkeypatch):
    from wittgenstein_tpu.serve import registry
    c = registry.CompileCounters()
    ev = "/jax/core/compile/jaxpr_trace_duration"
    c._on_span(ev, 1.0, 2.0)            # an inner jit, traced first
    c._on_span(ev, 0.5, 3.0)            # its caller
    c._on_span(ev, 5.0, 6.0)
    c._on_span("/jax/other", 0.0, 9.0)
    assert c.totals()["trace_s"] == pytest.approx(3.5)
    # a long life keeps a bounded tail of spans, the totals whole
    monkeypatch.setattr(registry, "KEEP_SPANS", 4)
    for i in range(100):
        c._on_span(ev, 10.0 + i, 10.5 + i)
    assert len(c._spans["trace_s"]) == 4
    assert c.totals()["trace_s"] == pytest.approx(3.5 + 50.0)
    c._on_span(ev, 108.0, 110.0)        # encloses the last two kept
    assert c.totals()["trace_s"] == pytest.approx(3.5 + 49.0 + 2.0)


def test_compile_counters_reach_the_metrics_exposition():
    ins = Instrumentation(worker="t")
    sch = Scheduler(instrument=ins)
    rid = sch.submit(ScenarioSpec(protocol="PingPong",
                                  params={"node_count": 48}, seeds=(0,),
                                  sim_ms=40, chunk_ms=40))
    sch.run_pending()
    assert sch.request(rid).status == "done"
    m = parse_exposition(scheduler_exposition(sch))
    for name in COMPILE_COUNTERS.values():
        assert name in m, name
    assert m["wtpu_compile_trace_seconds_total"] > 0
    assert m["wtpu_compile_backend_seconds_total"] > 0
