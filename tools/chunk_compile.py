"""What a benchmark cell's chunk program costs to build, plain and
phase-specialized.

For the two forms of the batched chunk program a cell can run —
`scan_chunk_batched(proto, chunk_ms)` (plain) and `scan_chunk_batched(
proto, chunk_ms, t0_mod=0)` (compact, the form `CompileRegistry` builds
when the protocol declares a schedule) — print one JSON line each with:

  * ``trace_lower_s`` and ``mlir_lines`` on this host's backend;
  * with ``--v5e``, the compile for a described TPU v5e (no chip needed:
    `jax.experimental.topologies`): ``compile_s``, the program's
    ``code_bytes`` (it sits in HBM beside the state) and ``temp_bytes``,
    and for each scan body of the compiled module the sorts, scatters
    and fusions it runs (``bodies``: the entry computation, then the scan
    bodies, widest carry first; a count stops at the scan bodies nested
    in it).

    JAX_PLATFORMS=cpu python tools/chunk_compile.py \\
        handel-cardinal-65536.stream1 --v5e
    JAX_PLATFORMS=cpu python tools/chunk_compile.py \\
        handel2048-exact.stream16 --nodes 512 --v5e --form plain

``--nodes N`` cuts the cell's node count (nodes down and threshold
scaled alike) for a quicker compile: the op counts per body do not
depend on it.  Only one process may describe the v5e at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

OPCODES = ("sort", "scatter", "fusion")


def cell_spec(name: str, nodes: int | None = None):
    """The cell's resolved `ScenarioSpec`, from BENCHMARK.json and its
    configuration and traffic files."""
    from benchmark.kinds.stream import spec_of
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((REPO / cfg["file"]).read_text())
    mix = json.loads((REPO / "benchmark" / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    if nodes:
        params = config["spec"]["params"]
        scale = nodes / params["node_count"]
        params.update(node_count=nodes,
                      nodes_down=int(params["nodes_down"] * scale),
                      threshold=int(params["threshold"] * scale))
    return spec_of(config, mix)


def body_ops(hlo_text: str) -> list[dict]:
    """Per scan body of a compiled module, and for the entry computation
    (where XLA inlines a scan of one step): its opcode counts, over the
    computations it calls, not descending into nested scan bodies."""
    from wittgenstein_tpu.analysis import hlo
    comps = hlo.parse_computations(hlo_text)
    bodies = hlo.scan_bodies(hlo_text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo_text, re.M).group(1)
    called = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
    # the result shape may be a tuple, with spaces: take the first
    # " opcode(" after the "="
    op = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")
    out = []
    for name in [entry, *bodies]:
        counts = dict.fromkeys(OPCODES, 0)
        seen, todo = {name}, [name]
        while todo:
            for line in comps.get(todo.pop(), "").splitlines():
                m = op.match(line)
                if m and m.group(1) in counts:
                    counts[m.group(1)] += 1
                for sub in called.findall(line):
                    if sub not in seen and sub not in bodies:
                        seen.add(sub)
                        todo.append(sub)
        out.append({"body": name, **counts})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", help="a workload name of BENCHMARK.json")
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--v5e", action="store_true",
                    help="also compile for a described TPU v5e")
    ap.add_argument("--form", choices=("plain", "compact", "both"),
                    default="both")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import wittgenstein_tpu.models  # noqa: F401 — fill the registry
    from wittgenstein_tpu.core.batched import scan_chunk_batched

    spec = cell_spec(args.cell, args.nodes)
    proto = spec.build_protocol()
    state = jax.eval_shape(jax.vmap(proto.init),
                           jnp.arange(len(spec.seeds), dtype=jnp.int32))
    v5e = None
    if args.v5e:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        v5e = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=SingleDeviceSharding(
                topo.devices[0])), state)
    for form, t0_mod in (("plain", None), ("compact", 0)):
        if args.form not in (form, "both"):
            continue
        fn = scan_chunk_batched(proto, spec.chunk_ms, t0_mod=t0_mod,
                                superstep=spec.superstep)
        t = time.perf_counter()
        lowered = jax.jit(fn).lower(*state)
        row = {"cell": args.cell,
               "node_count": proto.node_count, "form": form,
               "trace_lower_s": time.perf_counter() - t,
               "mlir_lines": lowered.as_text().count("\n")}
        if v5e is not None:
            t = time.perf_counter()
            compiled = jax.jit(fn).lower(*v5e).compile()
            mem = compiled.memory_analysis()
            row.update(compile_s=time.perf_counter() - t,
                       code_bytes=mem.generated_code_size_in_bytes,
                       temp_bytes=mem.temp_size_in_bytes,
                       bodies=body_ops(compiled.as_text()))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
