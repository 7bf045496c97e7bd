"""Rule ``carry_copy`` — scan-carry copy/DUS churn inside the compiled
superstep's while body.

The round-5 regression this enforces: when XLA's copy-insertion pass
cannot prove the mailbox ring scatters run after the inbox slices, it
copies EVERY ring plane once per superstep — measured 40 plane copies
per while body (~31% of step time) before the ordering barrier fix in
core/batched.py, 2 after (reports/PROFILE_r4.md, tools/carry_audit.py).
CPU HLO shows the same copy-insertion decisions, so the gate runs
anywhere.

Metrics (budgeted per protocol, ratchet-down):
  plane_copies    — copies whose shape matches a ring data/src/size
                    plane leaf (the exact regression signature);
  boxcount_copies — copies matching the box_count plane (also behind
                    the barrier; shape can collide with protocol
                    leaves, hence its own budget);
  copy_bytes      — total bytes of all >= 1 KB copies in scan bodies
                    (sub-KB copies are CPU scalar-loop noise);
  dus_bytes       — total dynamic-update-slice bytes in scan bodies;
  body_plane_copies, body_boxcount_copies
                  — the same counts in the one scan body that copies
                    most: what one superstep pays, where a program has
                    more than one body (the phase-specialized batched
                    scan's lead block and its inner rest scan).

Counts are summed across every scan-shaped while body in the module
(nested CPU scatter loops are excluded by the carry-width cut —
analysis/hlo.scan_bodies).
"""

from __future__ import annotations

import collections
import dataclasses

from . import hlo
from .framework import Finding, Rule, register_rule

_NOISE_BYTES = 1024     # ignore sub-KB copies (CPU loop-carried scalars)


@dataclasses.dataclass(frozen=True)
class AuditRow:
    body: str
    op: str             # "copy" | "dynamic-update-slice"
    shape: str
    count: int
    bytes: int
    leaf: str           # attributed state field names ("" when unknown)
    source: str


def audit(target) -> list[AuditRow]:
    """The detailed per-op view (what tools/carry_audit.py prints):
    every copy/DUS inside each scan while body, aggregated by
    (op, shape, source), attributed to state leaves by shape."""
    names = target.leaf_names
    rows: collections.Counter = collections.Counter()
    sizes: collections.Counter = collections.Counter()
    comps = hlo.parse_computations(target.hlo_text)
    for body_name in hlo.scan_bodies(target.hlo_text):
        body = comps.get(body_name, "")
        for op in hlo.iter_sized_ops(body, ("copy", "dynamic-update-slice")):
            leaf = "/".join(sorted(names.get(op.shape, []))[:3])
            key = (body_name, op.op, op.shape, leaf, op.source)
            rows[key] += 1
            sizes[key] += op.bytes
    return [AuditRow(body=k[0], op=k[1], shape=k[2], leaf=k[3], source=k[4],
                     count=c, bytes=sizes[k])
            for k, c in sorted(rows.items(), key=lambda kv: -sizes[kv[0]])]


def _is_plane(leaf: str) -> bool:
    return "box_data" in leaf or "box_src" in leaf or "box_size" in leaf


def metrics_from_rows(rows) -> dict:
    """The budgeted metrics, from an `audit` row list.

    `plane_copies` counts only the ring data/src/size planes — the
    round-5 regression signature with an unambiguous shape match.
    `boxcount_copies` separately tracks the smaller box_count plane
    (also behind the ordering barrier; its [R, H, N] shape can collide
    with protocol leaves like Handel's emission block, so it gets its
    own budget instead of diluting the strict plane gate)."""
    plane = collections.Counter()
    boxcount = collections.Counter()
    for r in rows:
        if r.op == "copy" and _is_plane(r.leaf):
            plane[r.body] += r.count
        if r.op == "copy" and "box_count" in r.leaf:
            boxcount[r.body] += r.count
    plane_copies = sum(plane.values())
    boxcount_copies = sum(boxcount.values())
    copy_bytes = sum(r.bytes for r in rows
                     if r.op == "copy" and r.bytes // r.count >= _NOISE_BYTES)
    dus_bytes = sum(r.bytes for r in rows if r.op == "dynamic-update-slice")
    return {"plane_copies": plane_copies,
            "boxcount_copies": boxcount_copies,
            "copy_bytes": copy_bytes, "dus_bytes": dus_bytes,
            "body_plane_copies": max(plane.values(), default=0),
            "body_boxcount_copies": max(boxcount.values(), default=0)}


def measure(target) -> dict:
    """The budgeted metrics for one target."""
    return metrics_from_rows(audit(target))


@register_rule
class CarryCopyRule(Rule):
    name = "carry_copy"
    scope = "protocol"
    budgeted_metrics = ("plane_copies", "boxcount_copies", "copy_bytes",
                        "dus_bytes", "body_plane_copies",
                        "body_boxcount_copies")

    def run(self, target, budget):
        if not hlo.scan_bodies(target.hlo_text):
            return [Finding(rule=self.name, target=target.name,
                            severity="warning",
                            message="no scan-shaped while body found in "
                                    "the compiled superstep")]
        metrics = measure(target)
        return [Finding(rule=self.name, target=target.name, severity="info",
                        metric=m, value=v,
                        message=f"{m}={v} in the scan while body")
                for m, v in metrics.items()]
