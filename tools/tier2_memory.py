"""Tier-2 (65k-131k) device working-set accounting -> reports/TIER2_MEMORY.md.

Computes the EXACT device residency of the tier-2 Handel configurations
from `jax.eval_shape` (no allocation): per-leaf bytes, the donated-vs-
undonated step peak, and the chips-needed verdict against v5e HBM
(16 GB/chip).  Complements reports/TIER2_CPU.md (round-2 host-RSS
measurement, which included XLA compile workspace and host copies —
device residency is what HBM sizing needs).

Usage: python tools/tier2_memory.py
"""

import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from wittgenstein_tpu.utils.platform import force_virtual_cpu  # noqa: E402

force_virtual_cpu(1)

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402

from wittgenstein_tpu.models.handel import Handel      # noqa: E402

HBM_PER_CHIP = 16e9          # v5e


def account(proto, label):
    shapes = jax.eval_shape(proto.init, jnp.asarray(0, jnp.int32))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    rows = [(jax.tree_util.keystr(p), x.size * x.dtype.itemsize)
            for p, x in leaves]
    rows.sort(key=lambda r: -r[1])
    total = sum(b for _, b in rows)
    big = sum(b for _, b in rows if b >= 1 << 20)
    # Peak without donation: input + output state live together (2x);
    # with the whole state donated (Runner's default) every leaf is
    # reused in place, so peak ~ total + a transient margin (XLA
    # temporaries are dominated by the largest sort/scatter operands,
    # ~1 extra copy of the ring slice).
    peak_nodonate = 2 * total
    peak_donated = total + 0.25 * big
    print(f"\n== {label}: total {total / 1e9:.2f} GB "
          f"(big leaves {big / 1e9:.2f} GB)")
    for name, b in rows[:8]:
        print(f"   {b / 1e9:7.3f} GB  {name}")
    return {"label": label, "rows": rows, "total": total, "big": big,
            "peak_nodonate": peak_nodonate, "peak_donated": peak_donated}


def main():
    cfgs = []
    for n in (65536, 131072):
        down = n // 10
        proto = Handel(
            node_count=n, nodes_down=down,
            threshold=int(0.99 * (n - down)), pairing_time=4,
            dissemination_period_ms=20, fast_path=10,
            emission_mode="hashed", snapshot_pool=False,
            queue_cap=(2 ** 31 - 1) // (n * ((n + 31) // 32)),
            inbox_cap=8, horizon=256)
        cfgs.append(account(proto, f"exact-hashed {n}"))
        from wittgenstein_tpu.models.handel_cardinal import HandelCardinal
        protoc = HandelCardinal(
            node_count=n, nodes_down=down,
            threshold=int(0.99 * (n - down)), pairing_time=4,
            dissemination_period_ms=20, fast_path=10, queue_cap=8,
            inbox_cap=8, horizon=256)
        cfgs.append(account(protoc, f"cardinal {n}"))

    lines = [
        "# Tier-2 device working set (exact accounting, jax.eval_shape)",
        "",
        "State bytes per seed for the tier-2 Handel configs (hashed",
        "emission, pool-free, horizon 256, inbox 8; queue_cap at the",
        "int32-index ceiling for exact mode, 8 for cardinal).  Peaks:",
        "undonated step = 2x state (input + output buffers both live);",
        "with the whole state donated (Runner's default) every leaf is",
        "reused in place, leaving a ~25% transient margin on the big ones.",
        "",
        "| config | state GB | peak (no donation) | peak (donated) |"
        " v5e chips (16 GB) |",
        "|---|---|---|---|---|",
    ]
    for c in cfgs:
        chips = max(1, int(-(-c["peak_donated"] // HBM_PER_CHIP)))
        lines.append(
            f"| {c['label']} | {c['total'] / 1e9:.2f} "
            f"| {c['peak_nodonate'] / 1e9:.2f} "
            f"| {c['peak_donated'] / 1e9:.2f} | {chips} |")
    lines += [
        "",
        "Top leaves (exact-hashed 65536):",
        "",
        "```",
    ]
    for name, b in cfgs[0]["rows"][:8]:
        lines.append(f"{b / 1e9:7.3f} GB  {name}")
    lines += [
        "```",
        "",
        "The verification queue (`q_sig`) and the mailbox ring dominate",
        "exact mode, as SCALE.md predicted; cardinal mode removes every",
        "O(N^2) leaf and drops tier-2 residency by an order of magnitude —",
        "its 131k config fits ONE chip with donation.  Round-2's 42.9 GB",
        "host RSS at 65k (reports/TIER2_CPU.md) was host-side (XLA",
        "compile workspace + host copies), not device residency.",
    ]
    out = REPO / "reports" / "TIER2_MEMORY.md"
    out.write_text("\n".join(lines) + "\n")
    print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
