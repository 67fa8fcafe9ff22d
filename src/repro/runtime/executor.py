"""The shared stage-program executor core.

One engine runs every scanned pipeline in the repo. Backends
(`runtime/pipeline.py`, `runtime/encdec_pipeline.py`,
`runtime/serve_step.py`) are thin adapters that build a
:class:`~repro.runtime.program.StageProgram` with a backend-specific
``tick`` hook; everything schedule-shaped lives here:

* :func:`run_stage_program` — the ``lax.scan`` tick loop (tick count and
  per-tick ``(item, virtual stage)`` mapping come from the program's
  schedule backend, mirroring ``repro.core.schedule.ScheduleSpec.
  tick_coords`` in traced arithmetic) and the left-neighbor ``ppermute``
  stage hand-off (backward = the autodiff transpose: reverse tick order,
  reversed ppermute, context-carry cotangents — the paper's dKV
  dependency, Eq. 5);
* :func:`run_stage_layers` — remat-split per-stage layer execution: the
  solver-chosen leading ``l_ckpt`` layers run under ``jax.checkpoint``
  (layer-granular recomputation, Eq. 9-11), the rest keep activations.
  ``l_ckpt`` may be a static int (one split point baked into the scan) or
  a traced scalar — the stage-aware per-(stage, chunk) policy, where
  :func:`remat_tick_count` looks the active depth up from the plan's
  checkpoint table at every tick;
* :func:`reset_ssm_at_boundary` — the split-chunk context-carry rule: a
  chunk with ``ctx_len == 0`` starts a new sequence, so SSM state resets
  (KV buffers reset implicitly by overwriting from offset 0);
* :func:`fold_streaming_ce` / :func:`fold_greedy_ids` — last-stage output
  folding into the scan accumulator (streaming vocab-parallel CE for
  training; greedy next-token ids for prefill/decode).

Bubble ticks compute on garbage (seg = -1 masks attention and loss): the
lockstep-SPMD analogue of pipeline bubbles. They inflate compiled HLO FLOPs
by ``spec.scan_ticks(n, d_p) / (n * v)`` — ``(n + d_p - 1)/n`` for plain
1F1B, divided by ~``v`` under ``interleaved-1f1b`` because every tick is
``1/v`` of a stage — the roofline's MODEL_FLOPS ratio surfaces this.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import sp
from .program import StageProgram, TickContext

__all__ = ["run_stage_program", "run_stage_layers", "ppermute_streams",
           "schedule_tick_coords", "remat_tick_count",
           "canonical_ckpt_table", "split_backward_stage", "make_stash",
           "reset_ssm_at_boundary", "fold_streaming_ce", "fold_greedy_ids"]


def canonical_ckpt_table(table, *, d_p: int, n_chunks: int):
    """Validate + canonicalize a per-(stage, chunk) checkpoint table to the
    hashable ``(d_p, n_chunks)`` tuple-of-tuples the frozen geometries
    store (None passes through: the uniform policy). The single shape
    gatekeeper for every geometry factory and ``__post_init__`` — a wrong
    shape must fail loudly before it is baked into a compiled step."""
    if table is None:
        return None
    out = tuple(tuple(int(v) for v in row) for row in table)
    if len(out) != d_p or any(len(r) != n_chunks for r in out):
        raise ValueError(
            f"ckpt_table must be (d_p={d_p}, n_chunks={n_chunks}); got "
            f"({len(out)}, {sorted(set(len(r) for r in out))})")
    return out


def schedule_tick_coords(t, p_idx, *, n: int, d_p: int, v: int,
                         n_groups: int):
    """``(idx, v_idx, valid)`` for tick ``t`` on device ``p_idx`` — the
    engine-side mirror of ``repro.core.schedule.ScheduleSpec.tick_coords``.

    Written in overloaded arithmetic only (floor ``//`` / ``%``), so it
    evaluates identically on traced jnp scalars inside the scan and on
    plain python ints — ``tests/test_schedule_backends.py`` sweeps both
    against the spec to keep executor and simulator in lockstep.
    """
    u = t - p_idx
    if v == 1:
        return u, 0, (u >= 0) & (u < n)
    r = u // d_p               # floor division: negative u stays invalid
    q = u - r * d_p
    v_idx = r % v
    idx = (r // v) * d_p + q
    valid = (u >= 0) & (u < n_groups * v * d_p) & (idx < n)
    return idx, v_idx, valid


def remat_tick_count(table, p_idx, idxc, valid, *, v: int = 1,
                     l_max: int = None):
    """Active remat depth for the ``(stage, virtual-stage, chunk)`` a tick
    runs — the engine-side lookup into the solver's per-(stage, chunk)
    checkpoint table (Eq. 9-11 made stage-aware).

    ``table`` is a ``(d_p, n_chunks)`` integer array; like
    :func:`schedule_tick_coords` this is written in overloaded arithmetic
    only (indexing + ``*`` / floor ``//``), so it evaluates identically on
    traced jnp scalars inside the scan and on plain ints/NumPy in the
    host-side simulators and tests — PROVIDED ``idxc`` is the CLIPPED
    in-range item index (``TickContext.idxc``, never the raw ``idx``):
    bubble ticks carry out-of-range raw indices that jnp would clamp but
    NumPy would reject. Bubble ticks (``valid`` False) remat nothing; with
    ``v`` virtual stages the stage's budget splits ``ceil(l / v)`` per
    block — the same memory-safe rounding the uniform path uses
    (over-remat bounded by ``v - 1`` layers). ``l_max`` clips to the
    block's layer count.
    """
    l = table[p_idx, idxc] * valid
    if v > 1:
        l = -((-l) // v)
    if l_max is not None:
        l = l + (l_max - l) * (l > l_max)   # min(l, l_max), overloaded
    return l


def ppermute_streams(streams, data_axis: str, d_p: int, *,
                     ring: bool = False):
    """Left-neighbor hand-off: every stream leaf moves stage p -> p + 1.

    ``ring=True`` closes the loop (``d_p - 1 -> 0``) — interleaved
    schedules route a chunk leaving the last device back to the first
    device's next virtual stage.
    """
    if d_p <= 1:
        return streams
    from repro.core.schedule import stream_perm
    perm = stream_perm(d_p, ring=ring)
    return jax.tree.map(
        lambda x: jax.lax.ppermute(x, data_axis, perm), streams)


# ---------------------------------------------------------------------------
# Split backward (zero-bubble B-grad / W-grad): the stage wrapper and the
# W-drain tick map.
#
# The lockstep scan runs ONE tick HLO on every device, so a masked or
# conditional W-grad inside the existing ticks realizes nothing — the win
# needs ticks with *different* HLO. The compiled structure:
#
# * every forward tick wraps its stage computation in
#   :func:`split_backward_stage` — a ``jax.custom_vjp`` whose forward is the
#   unmodified stage math (loss stays bitwise-identical) saving the stage's
#   ``jax.vjp`` closure as residuals, and whose backward (the B-grad tick)
#   applies the saved vjp, returns ONLY the input/context cotangents —
#   dropping the weight cotangents, so XLA dead-code-eliminates exactly the
#   wgrad GEMMs off the critical path — and pushes the boundary pair
#   ``(x_in, ctx_in, ybar, ctx_bar)`` into a per-item stash slot;
# * the stash rides the scan carry as a *cotangent mailbox*: its primal is
#   dead zeros threaded through untouched, while its cotangent accumulates
#   the pushed entries as the transposed scan walks ticks in reverse;
# * ``spec.drain_ticks`` dedicated W-grad ticks are prepended to the
#   *primal* program as a no-op scan (:func:`_run_drain_scan`) feeding the
#   stash into the forward scan — so in the autodiff transpose they run
#   LAST, exactly the backward cooldown, each popping one slot and
#   computing that item's stage weight grads (ZB-H1's W-grad fill, now in
#   the HLO).
#
# Bubble ticks push nothing (the push is valid-masked); their weight-grad
# contribution is exactly zero in the fused transpose too — bubble outputs
# never reach the loss, so their cotangents are exact zeros — which keeps
# split-vs-fused gradients at parity (tests/test_split_backward.py).
# ---------------------------------------------------------------------------


def make_stash(entry_struct, n_slots: int):
    """Zero-filled stash: one buffer of ``n_slots`` rows per (non-None)
    leaf of ``entry_struct`` — the pytree a single
    :func:`split_backward_stage` push writes, i.e.
    ``(x_in, ctx_in, ybar_like_x, ctx_bar_like_ctx)``."""
    return jax.tree.map(
        lambda a: jnp.zeros((n_slots, *a.shape), a.dtype), entry_struct)


def _stash_push(stash, slot, entry, valid):
    """Write ``entry`` at row ``slot`` of every stash leaf; bubble ticks
    (``valid`` False) leave the stash untouched."""
    return jax.tree.map(
        lambda buf, leaf: buf.at[slot].set(
            jnp.where(valid, leaf.astype(buf.dtype), buf[slot])),
        stash, entry)


def split_backward_stage(stage_fn: Callable, x, ctx, params, stash, slot,
                         valid, aux=()):
    """Run ``stage_fn(x, ctx, params, aux) -> (y, new_ctx)`` with the
    zero-bubble B/W split.

    Forward: the unmodified stage computation (its ``jax.vjp`` closure is
    saved as the custom_vjp residuals — no recompute on the critical
    path); the stash passes through untouched. Backward (B-grad tick):
    apply the saved vjp, drop the weight cotangents (their GEMMs become
    dead code on this tick) and push ``(x, ctx, ybar, ctx_bar)`` into
    ``stash``'s cotangent at row ``slot`` — the W-drain ticks pop it
    during cooldown (see :func:`_run_drain_scan`).

    ``stage_fn`` must NOT close over any traced value: the custom_vjp's
    backward is re-traced at scan-transpose time, when closure-captured
    tracers of the (long-dead) forward scan trace are leaked garbage —
    everything per-tick comes in through ``aux``, a pytree of FLOAT arrays
    (cast integer values to float32 at the caller; exact below 2**24, and
    its zero cotangent then stays an ordinary float — custom_vjp cannot
    return cotangents for integer operands). ``slot``/``valid`` enter as
    float32 for the same reason. Returns ``(y, new_ctx, stash)``.
    """

    def _run(x, ctx, params, stash, slot_f, valid_f, aux):
        y, new_ctx = stage_fn(x, ctx, params, aux)
        return y, new_ctx, stash

    def _fwd(x, ctx, params, stash, slot_f, valid_f, aux):
        (y, new_ctx), f_vjp = jax.vjp(
            lambda xx, cc, pp: stage_fn(xx, cc, pp, aux), x, ctx, params)
        return (y, new_ctx, stash), (f_vjp, x, ctx, slot_f, valid_f)

    def _bwd(res, cots):
        f_vjp, x, ctx, slot_f, valid_f = res
        ybar, ctx_bar, stash_bar = cots
        xbar, ctxbar, _wbar = f_vjp((ybar, ctx_bar))  # _wbar dropped: DCE
        slot = slot_f.astype(jnp.int32)
        valid = valid_f > 0.5
        stash_bar = _stash_push(stash_bar, slot, (x, ctx, ybar, ctx_bar),
                                valid)
        wzero = jax.tree.map(jnp.zeros_like, params)
        return (xbar, ctxbar, wzero, stash_bar,
                jnp.zeros_like(slot_f), jnp.zeros_like(valid_f),
                jax.tree.map(jnp.zeros_like, aux))

    run = jax.custom_vjp(_run)
    run.defvjp(_fwd, _bwd)
    return run(x, ctx, params, stash,
               jnp.asarray(slot, jnp.float32),
               jnp.asarray(valid, jnp.float32), aux)


def _run_drain_scan(drain_tick: Callable, stage_params, init_stash,
                    n_drain: int, aux=()):
    """The split-backward W-grad tick map: a primal no-op scan over
    ``n_drain`` slots threading the stash through one custom_vjp per tick.

    In the transposed program these ticks run after every B-grad tick (the
    backward cooldown); tick ``j`` pops stash row ``j`` and calls
    ``drain_tick(j, entry, stage_params, aux) -> params-cotangent`` — the
    backend's weight-grad recomputation for that (item, virtual-stage)
    slot. The per-tick contributions accumulate into ``stage_params``'s
    cotangent through the scan transpose. Like
    :func:`split_backward_stage`, ``drain_tick`` must not close over
    traced values — batch lookups etc. come in through ``aux`` (float
    arrays only).
    """

    def _nop(stash, params, j_f, aux):
        return stash

    def _fwd(stash, params, j_f, aux):
        return stash, (params, j_f, aux)

    def _bwd(res, stash_bar):
        params, j_f, aux = res
        j = j_f.astype(jnp.int32)
        entry = jax.tree.map(lambda buf: buf[j], stash_bar)
        wbar = drain_tick(j, entry, params, aux)
        return (stash_bar, wbar, jnp.zeros_like(j_f),
                jax.tree.map(jnp.zeros_like, aux))

    drain = jax.custom_vjp(_nop)
    drain.defvjp(_fwd, _bwd)

    def body(stash, j):
        return drain(stash, stage_params, j.astype(jnp.float32), aux), None

    stash, _ = jax.lax.scan(body, init_stash, jnp.arange(n_drain))
    return stash


def run_stage_program(program: StageProgram, init_streams, init_state,
                      init_acc) -> Tuple[Any, Any, Any]:
    """Run one stage program: the scanned tick loop all backends share.

    The per-tick ``(idx, v_idx, valid)`` coordinates are the traced mirror
    of the schedule backend's ``tick_coords``:

    * ``v == 1`` (``gpipe-1f1b``, ``zero-bubble-h1``, interleaved at one
      virtual stage): the classic diagonal ``idx = t - p``;
    * ``v > 1`` (``interleaved-1f1b``): wave index ``u = t - p`` decomposes
      into round ``r = u // d_p`` and offset ``q = u % d_p``; the device
      runs local virtual stage ``v_idx = r % v`` on item
      ``(r // v) * d_p + q`` — items advance through the ``v * d_p``
      virtual-stage ring in round-robin groups of ``d_p``, and the stream
      ppermute closes into a full ring.

    Returns the final ``(streams, state, acc)``; ``acc`` is psummed over
    the pipeline axis when ``program.psum_acc`` (only the last stage folds
    real output, the rest contribute zeros / stale rows).

    Two optional program hooks extend the tick map:

    * ``program.fold`` — double-buffered stage hand-off: the tick hook
      only computes, the engine issues the stream ``ppermute`` against the
      carry's (second) receive buffer, and ``fold(tc, streams, state,
      acc)`` then folds the *pre-permute* buffer into the accumulator —
      the permute-independent fold work (the vocab-parallel CE matmul is
      the big one) overlaps the in-flight collective under the TPU
      compiler's async collectives (on by default). Same
      values, same per-value op order: losses stay bitwise-identical.
    * ``program.split_bwd`` — the zero-bubble B/W split: the engine runs
      ``spec.drain_ticks`` W-grad ticks (:func:`_run_drain_scan`) feeding
      a stash buffer into the forward scan's carry; the tick hook (called
      as ``tick(tc, streams, state, acc, stash)``) threads it through
      :func:`split_backward_stage`. In the transpose the W ticks run
      after the whole B-grad scan — the cooldown drain.
    """
    n, d_p, v = program.n_items, program.d_p, program.v
    n_groups = program.spec.n_groups(n, d_p)
    split = program.split_bwd

    def _tick(carry, t):
        if split:
            streams, state, acc, stash = carry
        else:
            streams, state, acc = carry
        p_idx = jax.lax.axis_index(program.data_axis)
        idx, v_idx, valid = schedule_tick_coords(
            t, p_idx, n=n, d_p=d_p, v=v, n_groups=n_groups)
        idxc = jnp.clip(idx, 0, n - 1)
        tc = TickContext(t=t, idx=idx, idxc=idxc, valid=valid, p_idx=p_idx,
                         n_items=n, d_p=d_p, v_idx=v_idx, v=v)
        if split:
            streams, state, acc, stash = program.tick(tc, streams, state,
                                                      acc, stash)
        else:
            streams, state, acc = program.tick(tc, streams, state, acc)
        sent = ppermute_streams(streams, program.data_axis, d_p,
                                ring=(v > 1))
        if program.fold is not None:
            # double-buffered hand-off: fold the pre-permute buffer while
            # the collective is in flight
            acc = program.fold(tc, streams, state, acc)
        if split:
            return (sent, state, acc, stash), None
        return (sent, state, acc), None

    if split:
        # one W tick per (item, virtual stage) — ``spec.drain_ticks`` for
        # split_bwd backends, but derived from the program geometry so the
        # split path also runs under fused-schedule names (parity tests)
        n_drain = n * v
        stash0 = _run_drain_scan(program.drain_tick, program.stage_params,
                                 program.init_stash, n_drain,
                                 aux=program.drain_aux)
        (streams, state, acc, _), _ = jax.lax.scan(
            _tick, (init_streams, init_state, init_acc, stash0),
            jnp.arange(program.n_ticks))
    else:
        (streams, state, acc), _ = jax.lax.scan(
            _tick, (init_streams, init_state, init_acc),
            jnp.arange(program.n_ticks))
    if program.psum_acc:
        acc = jax.tree.map(
            lambda a: jax.lax.psum(a, program.data_axis), acc)
    return streams, state, acc


def run_stage_layers(layer_body: Callable, carry, xs, *, l_ckpt,
                     n_layers: int):
    """Scan one stage's layers with the solver's remat split.

    ``layer_body(carry, per_layer) -> (carry, y)`` advances the chunk
    activation(s) through one layer; ``xs`` is any pytree whose leaves have
    leading dim ``n_layers`` (stacked layer params, per-layer context
    slices, masks). The first ``l_ckpt`` layers recompute in backward —
    only their input + un-freeable KV persist (Eq. 9) — the rest keep
    activations. Returns ``(carry, ys)`` with the two partial scans' ys
    concatenated back to leading dim ``n_layers`` (None leaves pass
    through).

    ``l_ckpt`` may be:

    * a static python int — the split point is baked into the trace as two
      partial scans (the uniform policy; unchanged, bitwise-stable path);
    * a traced scalar (the stage-aware per-(stage, chunk) policy, looked
      up per tick via :func:`remat_tick_count`) — one scan over all
      ``n_layers`` whose body selects per layer, via ``lax.cond`` on
      ``layer_idx < l_ckpt``, between the ``jax.checkpoint``-wrapped body
      and the plain one. Values and gradients are identical either way —
      remat never changes the math (tests/test_remat_parity.py) — only
      which residuals the backward rematerializes.
    """
    if isinstance(l_ckpt, (int, np.integer)):
        l_ck = max(0, min(l_ckpt, n_layers))

        def split(a, b):
            return jax.tree.map(lambda t: t[a:b], xs)

        ys_parts = []
        if l_ck > 0:
            body_ck = jax.checkpoint(layer_body, prevent_cse=False)
            carry, ys = jax.lax.scan(body_ck, carry, split(0, l_ck))
            ys_parts.append(ys)
        if l_ck < n_layers:
            carry, ys = jax.lax.scan(layer_body, carry,
                                     split(l_ck, n_layers))
            ys_parts.append(ys)
        if len(ys_parts) == 2:
            ys = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0) if a is not None
                else None, ys_parts[0], ys_parts[1],
                is_leaf=lambda t: t is None)
        else:
            ys = ys_parts[0]
        return carry, ys

    # traced l_ckpt: per-layer runtime selection between remat / plain
    body_ck = jax.checkpoint(layer_body, prevent_cse=False)
    remat_flags = jnp.arange(n_layers) < l_ckpt

    def body(c, per_layer):
        flag, xs_layer = per_layer
        return jax.lax.cond(flag, body_ck, layer_body, c, xs_layer)

    return jax.lax.scan(body, carry, (remat_flags, xs))


def reset_ssm_at_boundary(ctx, ctx_len):
    """SSM state resets at sequence starts (``ctx_len == 0``); KV buffers
    reset implicitly by appending from offset 0."""
    if getattr(ctx, "ssm_h", None) is None:
        return ctx
    return ctx._replace(ssm_h=jnp.where(ctx_len == 0, 0.0, ctx.ssm_h))


def fold_streaming_ce(tc: TickContext, h_last, head_w, tgt, seg, acc, *,
                      model_axis: str, vocab_true: int):
    """Fold one chunk into the streaming vocab-parallel CE accumulator.

    Only the last stage on a valid tick contributes; bubbles and earlier
    stages fold a fully-masked chunk (exactly zero loss and zero grad).
    ``acc`` is ``(loss_sum, n_valid)``.
    """
    ce_valid = (seg >= 0) & (tgt >= 0) & tc.valid & tc.is_last_stage
    l_sum, n_val = sp.sharded_ce(h_last, head_w, jnp.maximum(tgt, 0),
                                 ce_valid, model_axis,
                                 vocab_true=vocab_true)
    return acc[0] + l_sum, acc[1] + n_val


def fold_greedy_ids(tc: TickContext, h_last, head_w, ids_acc, *,
                    model_axis: str, vocab_true: int,
                    token_sharded: bool = False):
    """Fold one item's greedy next-token ids into ``ids_acc`` at row
    ``tc.idxc`` (prefill, the serving engine and pipelined decode share
    this).

    ``sharded_greedy``'s cross-rank argmax merge assumes every model rank
    holds the SAME tokens (true for decode, whose psum'd attention leaves
    ``h_last`` replicated). Prefill/engine hidden states are TOKEN-sharded
    over the model axis — pass ``token_sharded=True`` so the rows are
    all-gathered before the vocab-parallel argmax (same collective the
    streaming-CE fold already pays) and this rank's block sliced back out;
    without it the pmax/pmin merge compares argmax candidates of
    *different* tokens across ranks and the ids are garbage whenever
    ``d_s > 1``.
    """
    if token_sharded:
        loc = h_last.shape[0]
        h_g = jax.lax.all_gather(h_last, model_axis, axis=0, tiled=True)
        ids_full = sp.sharded_greedy(h_g, head_w, model_axis,
                                     vocab_true=vocab_true)
        off = jax.lax.axis_index(model_axis) * loc
        ids = jax.lax.dynamic_slice_in_dim(ids_full, off, loc, axis=0)
    else:
        ids = sp.sharded_greedy(h_last, head_w, model_axis,
                                vocab_true=vocab_true)
    sel = tc.valid & tc.is_last_stage
    new_ids = jnp.where(sel, ids, ids_acc[tc.idxc])
    return ids_acc.at[tc.idxc].set(new_ids)
