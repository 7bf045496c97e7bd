"""Fused Pallas routing megakernel — the mailbox ring binning of
`core/network._bin_into_ring` as ONE kernel instead of a two-pass
stable radix sort + F+2 flat scatter passes + a count scatter-add.

Why (BENCH_NOTES.md r8): the sort/scatter binning is the engine's
per-ms FIXED cost — ~48% of the per-ms step at the headline config by
the r8 two-point fit — and the superstep-K window only amortizes it
(one sort + one scatter pass per K ms).  This kernel is the ceiling
move (ROADMAP item 5): the K-window's concatenated outboxes stream
through VMEM once per destination block, where slot-rank assignment
and the ring-row writes happen in-register — the compiled chunk then
contains ZERO XLA sort/scatter ops for routing (the
`superstep_amortization` rule ratchets that to ~0 on the
`+pallas_route` analysis targets).

Semantics are copied from `_bin_into_ring` EXACTLY (bit-equality on
every ring plane, the count plane, and the dropped counter —
tests/test_pallas_route.py):

  * messages are grouped by (ring row, dest) and ranked in INPUT
    order within a group — identical to the XLA path's stable
    (rel, dest) sort, because rel -> rel % horizon is injective over
    any one binning batch: the engine's arrival contract keeps rel in
    [1, horizon-1] (per-ms + spill drain) or [K, horizon+K-2] (fused
    K-window, K <= floor+1) — at most horizon-1 distinct values, so
    two in-batch messages with equal (row, dest) always have equal
    (rel, dest) and the group ranks coincide;
  * slot = box_count[row, dest] + rank over ALL valid same-cell
    messages (dropped ones still consume rank — the XLA path's
    semantics), entry accepted iff slot < inbox_cap;
  * the count plane advances by the ACCEPTED entries only, and
    `n_dropped` counts valid entries whose cell was full.

Kernel shape: grid (seed, dest-block); each step holds the
[H, D, C] ring slab of its destination block in VMEM (in-place via
`input_output_aliases`) plus the full message vectors, and processes
the messages in ROUTE_CHUNK-sized waves — per wave the (row, dest)
group ranks come from a triangular pairwise match count and the
cross-wave/initial occupancy from a one-hot f32 matmul gather against
the running count slab (exact: every count is an integer < 2^24, see
the launcher guard), then a predicated scalar store loop writes the
accepted rows.  No sort anywhere.

Selection: `WTPU_PALLAS_ROUTE=1` (the XLA path stays the default —
`route_enabled()`), or the serve plane's per-spec `route_kernel`
program knob via `forced()`/`with_route()`.  Runs under Pallas
interpret mode on CPU (`interpret=backend != "tpu"`), so tier-1 pins
bit-identity without a TPU; the named `route_row_bytes()` VMEM cost
model goes through `_pick_block` like the three existing kernels and
is evaluated by the `vmem_budget` analysis rule at the shipped
configs (on-chip validation staged in tools/run_measurements_r9.sh).
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

from .pallas_merge import _VMEM_BUDGET, _pad_lanes, _pick_block

I32 = jnp.int32
F32 = jnp.float32

#: messages per in-kernel binning wave (the [chunk, chunk] pairwise
#: rank matrix and the [chunk, H] one-hot gather are the wave-sized
#: temporaries — route_fixed_bytes models them)
ROUTE_CHUNK = 256

#: one-hot count gathers run on f32 (the MXU path); exact only while
#: every count stays below 2^24, so the launcher refuses larger
#: batches (no real config is near it: the headline K=8 window is
#: ~1.6e5 messages)
_EXACT_LIMIT = 1 << 24

_override = threading.local()


def route_enabled() -> bool:
    """True iff the fused Pallas routing kernel should replace the XLA
    sort/scatter binning for programs traced NOW: an active `forced()`
    override (the serve plane's per-spec program knob) wins, else the
    `WTPU_PALLAS_ROUTE` env flag (default off — the XLA path remains
    the fallback until the kernel is chip-validated)."""
    ov = getattr(_override, "value", None)
    if ov is not None:
        return ov == "pallas"
    import os
    return os.environ.get("WTPU_PALLAS_ROUTE", "0") != "0"


@contextlib.contextmanager
def forced(kind: str):
    """Force the routing-kernel selection for programs traced inside
    the context: ``"pallas"`` | ``"xla"``.  Thread-local, so one serve
    worker's build cannot leak into another's."""
    if kind not in ("pallas", "xla"):
        raise ValueError(f"route kernel must be 'pallas' or 'xla', "
                         f"got {kind!r}")
    prev = getattr(_override, "value", None)
    _override.value = kind
    try:
        yield
    finally:
        _override.value = prev


def with_route(fn, kind: str):
    """Wrap a (possibly jitted) chunk callable so every call — and in
    particular its FIRST, tracing call — runs under `forced(kind)`.
    The serve registry wraps each compiled program with the spec's
    `route_kernel` so a process-level WTPU_PALLAS_ROUTE cannot flip
    what a compile key claims was built."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with forced(kind):
            return fn(*args, **kwargs)
    return call


def route_row_bytes(horizon: int, inbox_cap: int, payload_words: int,
                    chunk: int = ROUTE_CHUNK) -> int:
    """Per-DESTINATION-row VMEM cost model of `_route_kernel`: each
    dest in the grid block keeps its [H, C] slab of every ring plane
    (payload words + src + size) live twice (blocked input + aliased
    output copy), its count/run/acc columns, and its lane of the
    per-wave one-hot gather.  The lane (minor) axis is the C slot
    axis, which Mosaic pads to 128 — the dominant term for the
    shipped inbox_cap=12 configs.  Named so the analysis
    `vmem_budget` rule evaluates the SAME model the launcher budgets
    with (the merge-kernel convention); constants await the r9
    on-chip validation like the score/gsf models did."""
    slab = horizon * _pad_lanes(inbox_cap) * 4 * (payload_words + 2) * 2
    cnt = horizon * 4 * 4            # cnt in/out + run + acc columns
    wave = chunk * 4 * 2             # od one-hot column + masked copy
    return slab + cnt + wave


def route_fixed_bytes(m: int, payload_words: int,
                      chunk: int = ROUTE_CHUNK) -> int:
    """Block-size-INDEPENDENT VMEM of one kernel instance: the full
    message vectors (h/d/valid/src/size + payload words) and the
    wave-sized rank/gather temporaries.  `_pick_block` only scales
    the per-row term, so the launcher subtracts this from the budget
    separately."""
    vecs = (5 + payload_words) * m * 4
    wave = chunk * chunk * 4 * 2 + chunk * _pad_lanes(chunk) * 4
    return vecs + wave


def _make_kernel(f: int, cap: int, chunk: int, n_waves: int):
    """Kernel closure for one (payload_words, inbox_cap, wave) config.
    Ref layout (matches the launcher's in/out ordering):
      in : cnt, data*F, src, size, h, d, valid, msrc, msize, pay
      out: cnt, data*F, src, size, dropped      (ring refs aliased)
    """
    from jax.experimental import pallas as pl

    def kernel(*refs):
        cnt_in = refs[0]
        data_in = refs[1:1 + f]
        src_in, size_in = refs[1 + f], refs[2 + f]
        h_ref, d_ref, v_ref, sm_ref, zm_ref, pay_ref = refs[3 + f:9 + f]
        ocnt = refs[9 + f]
        data_out = refs[10 + f:10 + 2 * f]
        src_out, size_out = refs[10 + 2 * f], refs[11 + 2 * f]
        odrop = refs[12 + 2 * f]

        hzn, dblk = cnt_in.shape[1], cnt_in.shape[2]
        g = pl.program_id(1)
        cnt0 = cnt_in[0]                                    # [H, D]
        # Copy-through before the scatter writes: the aliased output
        # block must be fully defined in both interpret and Mosaic
        # lowering (aliasing makes it the same HBM buffer, but the
        # VMEM out block is written here, not prefilled).
        for fi in range(f):
            data_out[fi][...] = data_in[fi][...]
        src_out[...] = src_in[...]
        size_out[...] = size_in[...]

        tri = (jax.lax.broadcasted_iota(I32, (chunk, chunk), 1) <
               jax.lax.broadcasted_iota(I32, (chunk, chunk), 0))

        # One fori iteration per message wave (NOT Python-unrolled:
        # wave count scales with the binning batch, and an unrolled
        # body would grow the kernel linearly with K x out_deg x n —
        # the shapes are wave-invariant, so the loop carries only the
        # running (occupancy, accepted, dropped) accumulators).
        def wave(w, carry):
            run, acc, drop = carry
            lo = w * chunk
            hv = h_ref[0, pl.ds(lo, chunk)]
            dv = d_ref[0, pl.ds(lo, chunk)] - g * dblk
            member = (v_ref[0, pl.ds(lo, chunk)] != 0) & \
                (dv >= 0) & (dv < dblk)
            hv = jnp.where(member, hv, 0)
            dv = jnp.where(member, dv, 0)
            # In-wave rank: earlier (j < i) valid messages of the same
            # (row, dest) cell — the stable sort's in-group order is
            # input order, so a triangular pairwise count IS the rank.
            same = ((hv[:, None] == hv[None, :]) &
                    (dv[:, None] == dv[None, :]) &
                    member[:, None] & member[None, :])
            rank = jnp.sum((same & tri).astype(I32), axis=1)
            # Cross-wave + initial occupancy: gather run[h, d] per
            # message through one-hot matmuls (exact in f32 below
            # 2^24 — launcher-guarded).
            oh = (hv[:, None] ==
                  jax.lax.broadcasted_iota(I32, (chunk, hzn), 1))
            od = (dv[:, None] ==
                  jax.lax.broadcasted_iota(I32, (chunk, dblk), 1))
            ohf, odf = oh.astype(F32), od.astype(F32)
            prior = jnp.sum(
                jnp.where(od, jnp.dot(ohf, run.astype(F32),
                                      preferred_element_type=F32), 0.0),
                axis=1).astype(I32)
            slot = prior + rank
            ok = member & (slot < cap)
            run = run + jnp.dot(
                ohf.T, jnp.where(member[:, None], odf, 0.0),
                preferred_element_type=F32).astype(I32)
            acc = acc + jnp.dot(
                ohf.T, jnp.where(ok[:, None], odf, 0.0),
                preferred_element_type=F32).astype(I32)
            drop = drop + jnp.sum((member & ~ok).astype(I32))

            def store(i, _):
                @pl.when(ok[i])
                def _():
                    hh, dd, ss = hv[i], dv[i], slot[i]
                    for fi in range(f):
                        data_out[fi][0, hh, dd, ss] = pay_ref[0, fi,
                                                              lo + i]
                    src_out[0, hh, dd, ss] = sm_ref[0, lo + i]
                    size_out[0, hh, dd, ss] = zm_ref[0, lo + i]
                return 0

            jax.lax.fori_loop(0, chunk, store, 0)
            return run, acc, drop

        run, acc, drop = jax.lax.fori_loop(
            0, n_waves, wave,
            (cnt0, jnp.zeros_like(cnt0), jnp.zeros((), I32)))
        ocnt[0] = cnt0 + acc
        odrop[0, 0] = drop

    return kernel


def _pick_route_block(ns: int, m: int, horizon: int, cap: int,
                      f: int, chunk: int, enforce: bool = True) -> int:
    """Destination-block size within the VMEM cost model.

    A real (Mosaic) launch must also obey the TPU's (8, 128) tiling
    rule: the count block is [1, H, blk], so its last dimension `blk`
    must be a multiple of 128 or all `ns` destinations (the slab
    block's second-minor `blk` then satisfies the 8-rule too).  The
    largest such block that fits the budget wins; when none fits, the
    launcher refuses at build time, naming the rule and the XLA path.
    At the headline ring (H256 x C12, F=2) one destination costs about
    1 MB, so no legal block fits and the kernel is refused there.

    ``enforce=False`` (interpret mode — CPU tests at arbitrary ring
    shapes) only SHRINKS by the model and never raises: the
    interpreter has neither tiling nor scoped VMEM, and bit-identity
    coverage must not depend on a chip-sized config."""
    row = route_row_bytes(horizon, cap, f, chunk)
    fixed = route_fixed_bytes(m, f, chunk)
    if not enforce:
        blk = 256
        while blk > 1 and (ns % blk or fixed + blk * row > _VMEM_BUDGET):
            blk //= 2
        return blk
    legal = [ns] + [b for b in range(128 * ((ns - 1) // 128), 0, -128)
                    if ns % b == 0]
    for blk in legal:
        if fixed + blk * row <= _VMEM_BUDGET:
            return blk
    least = legal[-1]
    raise ValueError(
        f"pallas_route: no destination block obeys the TPU (8, 128) "
        f"tiling rule within the {_VMEM_BUDGET / 1e6:.1f} MB scoped-VMEM "
        f"budget: the count block's last dimension must be a multiple "
        f"of 128 or all {ns} destinations, and the smallest such block "
        f"({least}) needs {(fixed + least * row) / 1e6:.2f} MB (fixed "
        f"{fixed / 1e6:.2f} + {least} x {row / 1e6:.2f} per "
        "destination); use the XLA routing path (route_kernel='xla', "
        "WTPU_PALLAS_ROUTE=0)")


def _route_call(data_planes, src_plane, size_plane, cnt,
                h, d, v, msrc, msize, pay, *, horizon, cap, interpret):
    """One sub-plane's pallas launch.  Shapes: ring planes
    [R, H, ns, C]; cnt [R, H, ns]; message vectors [R, M] (d already
    plane-local); pay [R, F, M].  Returns (data', src', size', cnt',
    dropped [R]) — ring planes updated in place via
    `input_output_aliases`."""
    from jax.experimental import pallas as pl

    r, hzn, ns, c = data_planes[0].shape
    f = len(data_planes)
    m = h.shape[1]

    mc = min(ROUTE_CHUNK, -(-m // 128) * 128)
    mpad = -(-m // mc) * mc
    if mpad != m:
        padv = ((0, 0), (0, mpad - m))
        h = jnp.pad(h, padv)
        d = jnp.pad(d, padv)
        v = jnp.pad(v, padv)
        msrc = jnp.pad(msrc, padv)
        msize = jnp.pad(msize, padv)
        pay = jnp.pad(pay, ((0, 0), (0, 0), (0, mpad - m)))
    blk = _pick_route_block(ns, mpad, hzn, cap, f, mc,
                            enforce=not interpret)
    grid = (r, ns // blk)

    def slab(_):
        return pl.BlockSpec((1, hzn, blk, c), lambda rr, g: (rr, 0, g, 0))

    def col():
        return pl.BlockSpec((1, hzn, blk), lambda rr, g: (rr, 0, g))

    def vec():
        return pl.BlockSpec((1, mpad), lambda rr, g: (rr, 0))

    kernel = _make_kernel(f, cap, mc, mpad // mc)
    out_shape = (
        [jax.ShapeDtypeStruct((r, hzn, ns), I32)] +
        [jax.ShapeDtypeStruct((r, hzn, ns, c), I32) for _ in range(f)] +
        [jax.ShapeDtypeStruct((r, hzn, ns, c), I32),
         jax.ShapeDtypeStruct((r, hzn, ns, c), I32),
         jax.ShapeDtypeStruct((r, grid[1]), I32)])
    out_specs = ([col()] + [slab(fi) for fi in range(f)] +
                 [slab(None), slab(None),
                  pl.BlockSpec((1, 1), lambda rr, g: (rr, g))])
    in_specs = ([col()] + [slab(fi) for fi in range(f)] +
                [slab(None), slab(None),
                 vec(), vec(), vec(), vec(), vec(),
                 pl.BlockSpec((1, f, mpad), lambda rr, g: (rr, 0, 0))])
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={i: i for i in range(3 + f)},
        interpret=interpret,
    )(cnt, *data_planes, src_plane, size_plane,
      h, d, v, msrc, msize, pay)
    cnt_new = outs[0]
    data_new = outs[1:1 + f]
    src_new, size_new = outs[1 + f], outs[2 + f]
    dropped = jnp.sum(outs[3 + f], axis=1).astype(I32)      # [R]
    return data_new, src_new, size_new, cnt_new, dropped


def bin_into_ring_planes(box_data, box_src, box_size, box_count,
                         h, dest, src, size, payload, valid, *,
                         horizon: int, cap: int, n: int, split: int,
                         payload_words: int, seed_axis: bool = False,
                         interpret: bool | None = None):
    """Bin one batch of messages into the mailbox ring planes with the
    fused kernel — the drop-in plane-level core shared by
    `network._bin_into_ring`, `batched._batched_bin` and the sharded
    runner's local ring.

    Layout mirrors `NetState`: `box_data` is the F*P tuple of flat
    [H*Ns*C] planes (plane ``fi*P + j``), `box_src`/`box_size` the
    P-tuples, `box_count` [H, N]; with ``seed_axis=True`` every plane
    carries a leading [R] batch axis (the seed-folded engine's layout)
    and the returned dropped count is per-seed [R].  `h` is the ring
    row ``arrival % horizon``; `dest` must already be clipped to
    [0, n) for valid entries (the `_bin_into_ring` contract).
    Returns ``(box_data', box_src', box_size', box_count',
    n_dropped)``.
    """
    f, p = payload_words, split
    ns = n // p
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not seed_axis:
        (box_data, box_src, box_size) = (
            tuple(x[None] for x in box_data),
            tuple(x[None] for x in box_src),
            tuple(x[None] for x in box_size))
        box_count = box_count[None]
        h, dest, src, size, valid = (x[None] for x in
                                     (h, dest, src, size, valid))
        payload = payload[None]
    r, m = h.shape
    if m + cap >= _EXACT_LIMIT:
        raise ValueError(
            f"pallas_route: {m} messages per binning batch exceeds the "
            f"one-hot gather's f32-exact range (< {_EXACT_LIMIT}); use "
            "the XLA path (WTPU_PALLAS_ROUTE=0) for this configuration")
    v32 = valid.astype(I32)
    pay = jnp.transpose(payload, (0, 2, 1))                 # [R, F, M]
    data_new, src_new, size_new = list(box_data), list(box_src), \
        list(box_size)
    cnt_cols = []
    dropped = jnp.zeros((r,), I32)
    for j in range(p):
        planes_j = [box_data[fi * p + j].reshape(r, horizon, ns, cap)
                    for fi in range(f)]
        srcp = box_src[j].reshape(r, horizon, ns, cap)
        sizep = box_size[j].reshape(r, horizon, ns, cap)
        cnt_j = box_count[:, :, j * ns:(j + 1) * ns]
        d_j = dest - j * ns if j else dest
        dj_new, srcj, sizej, cntj, dropj = _route_call(
            planes_j, srcp, sizep, cnt_j, h, d_j, v32, src, size, pay,
            horizon=horizon, cap=cap, interpret=interpret)
        for fi in range(f):
            data_new[fi * p + j] = dj_new[fi].reshape(
                box_data[fi * p + j].shape)
        src_new[j] = srcj.reshape(box_src[j].shape)
        size_new[j] = sizej.reshape(box_size[j].shape)
        cnt_cols.append(cntj)
        dropped = dropped + dropj
    box_count_new = (cnt_cols[0] if p == 1 else
                     jnp.concatenate(cnt_cols, axis=2))
    if not seed_axis:
        return (tuple(x[0] for x in data_new),
                tuple(x[0] for x in src_new),
                tuple(x[0] for x in size_new),
                box_count_new[0], dropped[0])
    return (tuple(data_new), tuple(src_new), tuple(size_new),
            box_count_new, dropped)
