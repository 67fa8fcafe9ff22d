"""Executor-core refactor guarantees.

(a) The refactored decoder and enc-dec loss paths (thin adapters over
    runtime/executor.py's StageProgram engine) are bitwise-identical to the
    PRE-REFACTOR executors — frozen verbatim below as ``ref_*`` functions —
    on a tiny config.
(b) The plan-bucket compile cache hits on a second same-bucket plan and
    misses on a different bucket.
(c) Bucket-padding chunks (fully masked: seg = -1, targets = -1) contribute
    exactly zero loss and zero gradient.

Distributed cases run in SUBPROCESSES with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
session keeps seeing exactly one CPU device (see conftest.py).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.configs import get_arch
    from repro.models import DecoderLM, EncDecLM, LayerCtx
    from repro.models.layers import rms_norm, swiglu_apply
    from repro.runtime import TrainStepBuilder, make_geometry
    from repro.runtime import sp
    from repro.runtime.pipeline import (pipeline_loss_fn, _make_model,
                                        init_stage_ctx)
    from repro.runtime.sharding import (gather_layer_params,
                                        gather_stage_params,
                                        shard_dim_tree,
                                        stage_param_specs, batch_specs)
    from repro.runtime.train_step import prepare_params

    # =====================================================================
    # FROZEN pre-refactor decoder executor (verbatim from the seed's
    # runtime/pipeline.py: its own lax.scan tick loop, ppermute, remat
    # split and CE folding — the reference the refactor must reproduce
    # bitwise).
    # =====================================================================
    def _ref_run_stage_layers(model, geom, stage_params, shard_dims, x, ctx,
                              *, seg, pos, ctx_len, windows, active,
                              model_axis):
        def layer_body(x, per_layer):
            lp, w, act, lctx = per_layer
            lp_full = lp if geom.zero3_mode == "per_step" else \\
                gather_layer_params(lp, shard_dims, model_axis)
            x_new, new_ctx = model.layer_apply(
                lp_full, x, pos=pos, seg=seg, ctx=lctx, ctx_len=ctx_len,
                window=w)
            x_out = jnp.where(act, x_new, x)
            new_ctx = jax.tree.map(
                lambda new, old: jnp.where(act, new, old) if new is not None
                else None, new_ctx, lctx, is_leaf=lambda t: t is None)
            return x_out, new_ctx

        L_s = geom.layers_per_stage
        l_ck = max(0, min(geom.l_ckpt, L_s))

        def split(tree, a, b):
            return jax.tree.map(lambda t: t[a:b], tree)

        ctx_parts = []
        if l_ck > 0:
            body_ck = jax.checkpoint(layer_body, prevent_cse=False)
            x, ctx_a = jax.lax.scan(
                body_ck, x, (split(stage_params, 0, l_ck),
                             windows[:l_ck], active[:l_ck],
                             split(ctx, 0, l_ck)))
            ctx_parts.append(ctx_a)
        if l_ck < L_s:
            x, ctx_b = jax.lax.scan(
                layer_body, x, (split(stage_params, l_ck, L_s),
                                windows[l_ck:], active[l_ck:],
                                split(ctx, l_ck, L_s)))
            ctx_parts.append(ctx_b)
        if len(ctx_parts) == 2:
            new_ctx = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0) if a is not None
                else None, ctx_parts[0], ctx_parts[1],
                is_leaf=lambda t: t is None)
        else:
            new_ctx = ctx_parts[0]
        return x, new_ctx

    def ref_pipeline_loss_fn(cfg, geom, shard_dims, *, pod_axis,
                             data_axis="data", model_axis="model",
                             mode="train"):
        model = _make_model(cfg, geom, model_axis)
        s = cfg.spec
        L_pad = geom.d_p * geom.layers_per_stage
        win_flat = [cfg.layer_window(i) for i in range(s.n_layers)]
        win_flat += [0] * (L_pad - s.n_layers)
        windows_all = jnp.asarray(win_flat, jnp.int32).reshape(
            geom.d_p, geom.layers_per_stage)
        import numpy as _np
        active_all = jnp.asarray(
            (_np.arange(L_pad) < s.n_layers).reshape(geom.d_p,
                                                     geom.layers_per_stage))

        def loss_local(params, batch):
            p_idx = jax.lax.axis_index(data_axis)
            stage_params = jax.tree.map(lambda x: x[0], params["stages"])
            if geom.zero3_mode == "per_step":
                stage_params = gather_stage_params(stage_params, shard_dims,
                                                   model_axis)
            windows = windows_all[p_idx]
            active = active_all[p_idx]
            n, d_p = geom.n_chunks, geom.d_p
            cap_loc = batch["tokens"].shape[-1]
            dt = geom.compute_dtype

            tokens_a = batch["tokens"].reshape(n, cap_loc)
            targets_a = batch["targets"].reshape(n, cap_loc)
            seg_a = batch["seg"].reshape(n, cap_loc)
            pos_a = batch["pos"].reshape(n, cap_loc)
            ctxlen_a = batch["ctx_len"].reshape(n)

            fn_gamma = params["final_norm"]
            if fn_gamma.shape[0] != s.d_model:
                fn_gamma = jax.lax.all_gather(fn_gamma, model_axis, axis=0,
                                              tiled=True)
            head_w = params.get("unembed", params["embed"])

            ctx0 = init_stage_ctx(cfg, geom)
            x0 = jnp.zeros((cap_loc, s.d_model), dt)

            def tick(carry, t):
                x_recv, ctx, acc0_c, acc1_c = carry
                loss_acc = (acc0_c, acc1_c)
                idx = t - p_idx
                valid = (idx >= 0) & (idx < n)
                idxc = jnp.clip(idx, 0, n - 1)
                tokens = tokens_a[idxc]
                seg = jnp.where(valid, seg_a[idxc], -1)
                pos = pos_a[idxc]
                tgt = targets_a[idxc]
                ctx_len = jnp.where(valid, ctxlen_a[idxc], 0)

                x_emb = sp.sharded_embed(params["embed"], tokens,
                                         model_axis, dt)
                if cfg.embed_scale:
                    x_emb = x_emb * jnp.asarray(s.d_model ** 0.5, dt)
                x_in = jnp.where(p_idx == 0, x_emb, x_recv)

                if ctx.ssm_h is not None:
                    hh = jnp.where(ctx_len == 0, 0.0, ctx.ssm_h)
                    ctx = ctx._replace(ssm_h=hh)

                x_out, ctx = _ref_run_stage_layers(
                    model, geom, stage_params, shard_dims, x_in, ctx,
                    seg=seg, pos=pos, ctx_len=ctx_len, windows=windows,
                    active=active, model_axis=model_axis)

                h_last = rms_norm(x_out, fn_gamma, cfg.rms_eps)
                ce_valid = (seg >= 0) & (tgt >= 0) & valid \\
                    & (p_idx == d_p - 1)
                l_sum, n_val = sp.sharded_ce(h_last, head_w,
                                             jnp.maximum(tgt, 0), ce_valid,
                                             model_axis, vocab_true=s.vocab)
                out_acc = (loss_acc[0] + l_sum, loss_acc[1] + n_val)

                if d_p > 1:
                    x_send = jax.lax.ppermute(
                        x_out, data_axis,
                        [(i, i + 1) for i in range(d_p - 1)])
                else:
                    x_send = x_out
                return (x_send, ctx, out_acc[0], out_acc[1]), None

            acc0 = (jnp.float32(0), jnp.float32(0))
            init = (x0, ctx0, acc0[0], acc0[1])
            (xf, ctxf, a0, a1), _ = jax.lax.scan(
                tick, init, jnp.arange(n + d_p - 1))
            loss = jax.lax.psum(a0, data_axis)
            n_val = jax.lax.psum(a1, data_axis)
            return loss, n_val

        return loss_local

    # =====================================================================
    # Shared tiny-decoder fixture.
    # =====================================================================
    def decoder_case(l_ckpt=1, n_chunks=4, pad_chunks=0, cap=32,
                     schedule="gpipe-1f1b", v_stages=1):
        cfg = get_arch("llama3.2-3b").reduced(n_layers=4, d_model=64,
                                              n_heads=4, head_dim=16,
                                              vocab=256)
        mesh = make_mesh((2, 4), ("data", "model"))
        n = n_chunks + pad_chunks
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 256, (n_chunks, cap)).astype(np.int32)
        targets = rng.integers(0, 256, (n_chunks, cap)).astype(np.int32)
        seg = np.repeat(np.arange(n_chunks, dtype=np.int32)[:, None], cap, 1)
        pos = np.tile(np.arange(cap, dtype=np.int32), (n_chunks, 1))
        ctx_len = np.zeros((n_chunks,), np.int32)
        def padc(a, fill):
            out = np.full((n, *a.shape[1:]), fill, a.dtype)
            out[:n_chunks] = a
            return out
        batch = {"tokens": padc(tokens, 0), "targets": padc(targets, -1),
                 "seg": padc(seg, -1), "pos": padc(pos, 0),
                 "ctx_len": padc(ctx_len, 0)}
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        geom = make_geometry(cfg, mesh, n_chunks=n, cap=cap, ctx_cap=2 * cap,
                             l_ckpt=l_ckpt, compute_dtype=jnp.float32,
                             schedule=schedule, v_stages=v_stages)
        builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.float32)
        raw = DecoderLM(cfg).init(jax.random.PRNGKey(7), jnp.float32)
        params = prepare_params(cfg, raw, mesh, jnp.float32,
                                v_stages=v_stages)
        pspecs, _, bspecs = builder.specs(jax.eval_shape(lambda: params))
        shard_dims = shard_dim_tree(params["stages"], 4)
        return cfg, mesh, geom, params, batch, pspecs, bspecs, shard_dims

    def mapped_loss(loss_fn, mesh, pspecs, bspecs):
        return jax.jit(jax.shard_map(
            loss_fn, mesh=mesh, in_specs=(pspecs, bspecs),
            out_specs=(P(), P()), check_vma=False))
""")


def _run(case: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _COMMON + textwrap.dedent(case)],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}")
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# (a) bitwise equivalence: decoder path
# ---------------------------------------------------------------------------

def test_decoder_matches_prerefactor_bitwise():
    _run("""
        cfg, mesh, geom, params, batch, pspecs, bspecs, sd = decoder_case(
            l_ckpt=1)
        new = mapped_loss(pipeline_loss_fn(cfg, geom, sd, pod_axis=None),
                          mesh, pspecs, bspecs)
        ref = mapped_loss(ref_pipeline_loss_fn(cfg, geom, sd, pod_axis=None),
                          mesh, pspecs, bspecs)
        ln, nn = new(params, batch)
        lr, nr = ref(params, batch)
        assert float(nn) == float(nr), (nn, nr)
        assert np.asarray(ln).tobytes() == np.asarray(lr).tobytes(), \\
            (float(ln), float(lr))

        # gradients agree too (executor transpose == hand-rolled transpose)
        def scalar(fn):
            def s(p):
                l, n = fn(p, batch)
                return l / n
            return s
        gn = jax.grad(scalar(new))(params)
        gr = jax.grad(scalar(ref))(params)
        for a, b in zip(jax.tree.leaves(gn), jax.tree.leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        print("OK decoder bitwise", float(ln))
    """)


# ---------------------------------------------------------------------------
# (a) bitwise equivalence: enc-dec path
# ---------------------------------------------------------------------------

def test_encdec_matches_prerefactor_bitwise():
    _run("""
        import math
        from repro.kernels.ref import blocked_flash_attention
        from repro.models.attention import attention_block
        from repro.runtime.encdec_pipeline import (
            encdec_batch_struct, encdec_pipeline_loss_fn,
            make_encdec_geometry, prepare_encdec_params)

        # FROZEN pre-refactor enc-dec executor (verbatim from the seed's
        # runtime/encdec_pipeline.py tick loop).
        def ref_encdec_pipeline_loss_fn(cfg, geom, shard_dims, *, pod_axis,
                                        data_axis="data",
                                        model_axis="model"):
            s = cfg.spec
            d_p, d_s = geom.d_p, geom.d_s
            L_ps = geom.layers_per_stage
            enc_st = geom.enc_stages
            dec_st = d_p - enc_st
            dt = geom.compute_dtype
            self_policy = sp.make_allgather_kv_policy(model_axis)
            nc_policy = sp.make_allgather_kv_policy(model_axis)

            import numpy as _np
            act_enc = (_np.arange(enc_st * L_ps) < s.n_encoder_layers)
            act_dec = (_np.arange(dec_st * L_ps) < s.n_layers)
            active_all = jnp.asarray(
                _np.concatenate([act_enc, act_dec]).reshape(d_p, L_ps))
            scale = 1.0 / math.sqrt(s.head_dim)

            def _cross(lp, h, memory, seg_q, seg_mem):
                dtl = h.dtype
                Dh, Hq, Hkv = s.head_dim, s.n_heads, s.n_kv_heads
                q = jnp.einsum("td,dh->th", h, lp["wq"].astype(dtl)
                               ).reshape(-1, Hq, Dh)
                k = jnp.einsum("sd,dh->sh", memory, lp["wk"].astype(dtl)
                               ).reshape(-1, Hkv, Dh)
                v = jnp.einsum("sd,dh->sh", memory, lp["wv"].astype(dtl)
                               ).reshape(-1, Hkv, Dh)
                k = jax.lax.all_gather(k, model_axis, axis=0, tiled=True)
                v = jax.lax.all_gather(v, model_axis, axis=0, tiled=True)
                sm = jax.lax.all_gather(seg_mem, model_axis, axis=0,
                                        tiled=True)
                z_q = jnp.zeros((q.shape[0],), jnp.int32)
                z_k = jnp.zeros((k.shape[0],), jnp.int32)
                out = blocked_flash_attention(q, k, v, seg_q, sm, z_q, z_k,
                                              causal=False, window=0,
                                              scale=scale)
                return jnp.einsum("th,hd->td", out.reshape(h.shape[0], -1),
                                  lp["wo"].astype(dtl))

            def loss_local(params, batch):
                p_idx = jax.lax.axis_index(data_axis)
                stage_params = jax.tree.map(lambda x: x[0],
                                            params["stages"])
                active = active_all[p_idx]
                n = geom.n_chunks
                cap_loc = batch["tokens"].shape[-1]
                cape_loc = batch["frames"].shape[-2]
                is_enc = p_idx < enc_st

                head_w = params["embed"]
                fn_gamma = params["final_norm"]
                if fn_gamma.shape[0] != s.d_model:
                    fn_gamma = jax.lax.all_gather(fn_gamma, model_axis,
                                                  axis=0, tiled=True)
                en_gamma = params["enc_norm"]
                if en_gamma.shape[0] != s.d_model:
                    en_gamma = jax.lax.all_gather(en_gamma, model_axis,
                                                  axis=0, tiled=True)

                kcap = geom.ctx_cap
                ctx0 = LayerCtx(
                    jnp.zeros((L_ps, kcap, s.n_kv_heads, s.head_dim), dt),
                    jnp.zeros((L_ps, kcap, s.n_kv_heads, s.head_dim), dt),
                    None, None)

                def tick(carry, t):
                    h_enc, h_dec, ctx, loss_acc, n_acc = carry
                    idx = t - p_idx
                    valid = (idx >= 0) & (idx < n)
                    idxc = jnp.clip(idx, 0, n - 1)
                    tokens = batch["tokens"][idxc]
                    seg = jnp.where(valid, batch["seg"][idxc], -1)
                    pos = batch["pos"][idxc]
                    tgt = batch["targets"][idxc]
                    ctx_len = jnp.where(valid, batch["ctx_len"][idxc], 0)
                    seg_e = jnp.where(valid, batch["seg_enc"][idxc], -1)
                    pos_e = batch["pos_enc"][idxc]

                    h_enc = jnp.where(p_idx == 0, batch["frames"][idxc],
                                      h_enc)
                    x_emb = sp.sharded_embed(params["embed"], tokens,
                                             model_axis, dt)
                    h_dec = jnp.where(p_idx == enc_st, x_emb, h_dec)
                    h_enc = jnp.where(p_idx == enc_st,
                                      rms_norm(h_enc, en_gamma,
                                               cfg.rms_eps), h_enc)

                    def layer_body(carry2, per_layer):
                        he, hd = carry2
                        lp, act, lctx = per_layer
                        lp = gather_layer_params(lp, shard_dims, model_axis)
                        h1 = rms_norm(he, lp["ln1"], cfg.rms_eps)
                        eo, _, _ = attention_block(
                            cfg, lp["attn"], h1, pos=pos_e, seg=seg_e,
                            ctx_k=None, ctx_v=None, ctx_len=None, window=0,
                            attn_fn=nc_policy, causal=False)
                        he_new = he + eo
                        he_new = he_new + swiglu_apply(
                            lp["mlp"], rms_norm(he_new, lp["ln2"],
                                                cfg.rms_eps))
                        d1 = rms_norm(hd, lp["ln1"], cfg.rms_eps)
                        do, nk, nv = attention_block(
                            cfg, lp["attn"], d1, pos=pos, seg=seg,
                            ctx_k=lctx.k, ctx_v=lctx.v, ctx_len=ctx_len,
                            window=0, attn_fn=self_policy, causal=True)
                        hd_new = hd + do
                        hx = rms_norm(hd_new, lp["ln_x"], cfg.rms_eps)
                        hd_new = hd_new + _cross(lp["cross"], hx, h_enc,
                                                 seg, seg_e)
                        hd_new = hd_new + swiglu_apply(
                            lp["mlp"], rms_norm(hd_new, lp["ln2"],
                                                cfg.rms_eps))
                        he_out = jnp.where(act & is_enc, he_new, he)
                        hd_out = jnp.where(act & (~is_enc), hd_new, hd)
                        new_ctx = LayerCtx(
                            jnp.where(act & (~is_enc), nk, lctx.k),
                            jnp.where(act & (~is_enc), nv, lctx.v),
                            None, None)
                        return (he_out, hd_out), new_ctx

                    (h_enc2, h_dec2), new_ctx = jax.lax.scan(
                        layer_body, (h_enc, h_dec),
                        (stage_params, active, ctx))

                    h_last = rms_norm(h_dec2, fn_gamma, cfg.rms_eps)
                    ce_valid = (seg >= 0) & (tgt >= 0) & valid \\
                        & (p_idx == d_p - 1)
                    l_sum, n_val = sp.sharded_ce(h_last, head_w,
                                                 jnp.maximum(tgt, 0),
                                                 ce_valid, model_axis,
                                                 vocab_true=s.vocab)
                    loss_acc = loss_acc + l_sum
                    n_acc = n_acc + n_val
                    perm = [(i, i + 1) for i in range(d_p - 1)]
                    h_enc_s = jax.lax.ppermute(h_enc2, data_axis, perm)
                    h_dec_s = jax.lax.ppermute(h_dec2, data_axis, perm)
                    return (h_enc_s, h_dec_s, new_ctx, loss_acc, n_acc), None

                he0 = jnp.zeros((cape_loc, s.d_model), dt)
                hd0 = jnp.zeros((cap_loc, s.d_model), dt)
                init = (he0, hd0, ctx0, jnp.float32(0), jnp.float32(0))
                (he, hd, ctxf, loss, n_val), _ = jax.lax.scan(
                    tick, init, jnp.arange(n + d_p - 1))
                loss = jax.lax.psum(loss, data_axis)
                n_val = jax.lax.psum(n_val, data_axis)
                return loss, n_val

            return loss_local

        cfg = get_arch("seamless-m4t-v2").reduced(n_layers=2, d_model=64,
                                                  n_heads=4, head_dim=16,
                                                  vocab=256)
        mesh = make_mesh((2, 4), ("data", "model"))
        n, cap, cape = 3, 32, 32
        geom = make_encdec_geometry(cfg, mesh, n_chunks=n, cap=cap,
                                    cap_enc=cape, ctx_cap=2 * cap, l_ckpt=0,
                                    compute_dtype=jnp.float32)
        raw = EncDecLM(cfg).init(jax.random.PRNGKey(5), jnp.float32)
        params = prepare_encdec_params(cfg, raw, geom, jnp.float32)
        d_s = 4
        pspecs = {
            "stages": stage_param_specs(
                jax.eval_shape(lambda: params)["stages"], d_s, pod=None),
            "embed": P("model", None),
            "enc_norm": P("model"),
            "final_norm": P("model"),
        }
        shard_dims = shard_dim_tree(params["stages"], d_s)
        bstruct = encdec_batch_struct(geom, cfg, 1)
        bspecs = batch_specs(bstruct, pod=None, model="model")
        rng = np.random.default_rng(2)
        batch = {}
        for k, v in bstruct.items():
            if v.dtype == jnp.int32:
                if k.startswith("seg"):
                    arr = np.zeros(v.shape, np.int32)
                elif k.startswith("pos"):
                    arr = np.tile(np.arange(v.shape[-1], dtype=np.int32),
                                  (*v.shape[:-1], 1))
                elif k == "ctx_len":
                    arr = np.zeros(v.shape, np.int32)
                else:
                    arr = rng.integers(0, 256, v.shape).astype(np.int32)
            else:
                arr = rng.normal(0, 0.5, v.shape).astype(np.float32)
            batch[k] = jnp.asarray(arr)

        new = mapped_loss(
            encdec_pipeline_loss_fn(cfg, geom, shard_dims, pod_axis=None),
            mesh, pspecs, bspecs)
        ref = mapped_loss(
            ref_encdec_pipeline_loss_fn(cfg, geom, shard_dims,
                                        pod_axis=None),
            mesh, pspecs, bspecs)
        ln, nn = new(params, batch)
        lr, nr = ref(params, batch)
        assert float(nn) == float(nr), (nn, nr)
        assert np.asarray(ln).tobytes() == np.asarray(lr).tobytes(), \\
            (float(ln), float(lr))
        print("OK encdec bitwise", float(ln))
    """)


# ---------------------------------------------------------------------------
# (c) bucket-padding chunks contribute exactly zero loss/grad
# ---------------------------------------------------------------------------

def test_bucket_padding_zero_contribution():
    _run("""
        cfg, mesh, geom, params, batch, pspecs, bspecs, sd = decoder_case(
            l_ckpt=0, n_chunks=4, pad_chunks=0)
        cfgp, meshp, geomp, paramsp, batchp, pspecsp, bspecsp, sdp = \\
            decoder_case(l_ckpt=0, n_chunks=4, pad_chunks=4)

        def scalar(fn, b):
            def s(p):
                l, n = fn(p, b)
                return l / jnp.maximum(n, 1.0)
            return s
        f0 = mapped_loss(pipeline_loss_fn(cfg, geom, sd, pod_axis=None),
                         mesh, pspecs, bspecs)
        f1 = mapped_loss(pipeline_loss_fn(cfgp, geomp, sdp, pod_axis=None),
                         meshp, pspecsp, bspecsp)
        l0, n0 = f0(params, batch)
        l1, n1 = f1(paramsp, batchp)
        assert float(n0) == float(n1), (n0, n1)
        assert np.asarray(l0).tobytes() == np.asarray(l1).tobytes(), \\
            (float(l0), float(l1))
        g0 = jax.grad(scalar(f0, batch))(params)
        g1 = jax.grad(scalar(f1, batchp))(paramsp)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK padding", float(l0))
    """)


# ---------------------------------------------------------------------------
# (b) compile cache: hit on a same-bucket plan, miss on a different bucket
# ---------------------------------------------------------------------------

def test_bucket_cache_hit_and_miss():
    from repro.core import ClusterSpec, CostModel, ModelSpec, PlannerConfig, \
        plan_batch
    from repro.runtime.compile_cache import CompileCache

    m = ModelSpec(name="t", n_layers=8, d_model=256, n_heads=8, n_kv_heads=4,
                  head_dim=32, d_ff=1024, vocab=512)
    cm = CostModel(m, ClusterSpec(d_p=4, d_s=4))
    pc = PlannerConfig(bucket_rounding=64)
    plan_a = plan_batch(cm, [512, 384, 256, 256], pc)
    plan_b = plan_batch(cm, [512, 384, 256, 256], pc)   # same workload
    plan_c = plan_batch(cm, [8192, 4096, 512, 256], pc)  # different bucket

    d_s = 4
    assert plan_a.bucket_key(d_s) == plan_b.bucket_key(d_s)
    assert plan_a.bucket_key(d_s) != plan_c.bucket_key(d_s)

    builds = []
    cache = CompileCache(name="test")

    def make_build(tag):
        def build():
            builds.append(tag)
            return tag
        return build

    assert cache.get(plan_a.bucket_key(d_s), make_build("a")) == "a"
    assert cache.get(plan_b.bucket_key(d_s), make_build("b")) == "a"  # hit
    assert cache.get(plan_c.bucket_key(d_s), make_build("c")) == "c"  # miss
    assert builds == ["a", "c"]
    assert cache.stats.hits == 1 and cache.stats.misses == 2
    assert cache.stats.hit_rate == pytest.approx(1 / 3)


def test_bucket_key_named_fields():
    """bucket_key() returns a NamedTuple: consumers (launch/train.py,
    benchmarks) access geometry by NAME — positional slices like
    ``key[2:4]`` broke silently when PR 2 reordered the tuple."""
    from repro.core import BucketKey, ClusterSpec, CostModel, ModelSpec, \
        PlannerConfig, plan_batch

    m = ModelSpec(name="t", n_layers=8, d_model=256, n_heads=8,
                  n_kv_heads=4, head_dim=32, d_ff=1024, vocab=512)
    cm = CostModel(m, ClusterSpec(d_p=4, d_s=4))
    plan = plan_batch(cm, [512, 384, 256, 256],
                      PlannerConfig(bucket_rounding=64))
    key = plan.bucket_key(4)
    assert isinstance(key, BucketKey)
    assert BucketKey._fields == ("schedule", "v_stages", "n_chunks",
                                 "cap", "ctx_cap", "l_ckpt", "ckpt",
                                 "split_bwd", "dtype", "sp_policy",
                                 "d_s_eff")
    # named access agrees with the documented order (and stays a tuple:
    # hashable, comparable, usable as a cache key)
    assert key.schedule == key[0] == plan.schedule
    assert key.v_stages == key[1] == plan.v_stages
    assert key.n_chunks == key[2] and key.cap == key[3]
    assert key.ctx_cap == key[4] and key.l_ckpt == key[5]
    assert key.ckpt == key[6] == f"u{plan.uniform_ckpt()}"
    # the lowering-relevant plan axes added by the auditor PR: split_bwd
    # resolves "auto" through the schedule backend, dtype is a string
    assert isinstance(key.split_bwd, bool)
    assert key.dtype == "bfloat16"
    # the SP axis (PR 8): the planner's (policy, d_s_eff) is part of the
    # compile identity so SP-differing plans never alias executables
    assert key.sp_policy == plan.sp.policy
    assert key.d_s_eff == plan.sp.d_s_eff
    forced = plan.bucket_key(4, split_bwd="on", dtype="float32")
    assert forced.split_bwd is True and forced.dtype == "float32"
    assert forced != key or (key.split_bwd and key.dtype == "float32")
    # 4 real chunks key 4: a power of two up to chunk_rounding (8)
    assert plan.n_chunks == 4 and key.n_chunks == 4 and key.cap % 4 == 0
    assert hash(key) == hash(tuple(key))


def test_cache_eviction_lru():
    from repro.runtime.compile_cache import CompileCache
    cache = CompileCache(name="evict", capacity=2)
    cache.get(1, lambda: "one")
    cache.get(2, lambda: "two")
    cache.get(1, lambda: "one")       # refresh 1 -> 2 becomes LRU
    cache.get(3, lambda: "three")     # evicts 2
    assert cache.stats.evictions == 1
    assert 2 not in cache and 1 in cache and 3 in cache


# ---------------------------------------------------------------------------
# (d) schedule backends on the same fixtures: zero-bubble-h1 (W-grad fused)
#     and interleaved-1f1b at v=1 are bitwise-loss-identical to the default
#     1F1B executor; interleaved at v=2 computes the same model (virtual
#     stages ride the ring in layer order), so loss and grads match too.
# ---------------------------------------------------------------------------

def test_schedule_backends_bitwise_at_v1():
    _run("""
        cfg, mesh, geom, params, batch, pspecs, bspecs, sd = decoder_case(
            l_ckpt=1)
        base = mapped_loss(pipeline_loss_fn(cfg, geom, sd, pod_axis=None),
                           mesh, pspecs, bspecs)
        l0, n0 = base(params, batch)
        for schedule in ("zero-bubble-h1", "interleaved-1f1b"):
            cfg2, mesh2, geom2, params2, batch2, pspecs2, bspecs2, sd2 = \\
                decoder_case(l_ckpt=1, schedule=schedule, v_stages=1)
            fn = mapped_loss(
                pipeline_loss_fn(cfg2, geom2, sd2, pod_axis=None),
                mesh2, pspecs2, bspecs2)
            l1, n1 = fn(params2, batch2)
            assert float(n0) == float(n1), (schedule, n0, n1)
            assert np.asarray(l0).tobytes() == np.asarray(l1).tobytes(), \\
                (schedule, float(l0), float(l1))
        print("OK schedule backends bitwise", float(l0))
    """)


def test_interleaved_v2_matches_v1():
    _run("""
        from repro.runtime.sharding import unstack_stages
        cfg, mesh, geom, params, batch, pspecs, bspecs, sd = decoder_case(
            l_ckpt=1)
        cfg2, mesh2, geom2, params2, batch2, pspecs2, bspecs2, sd2 = \\
            decoder_case(l_ckpt=1, schedule="interleaved-1f1b", v_stages=2)
        f1 = mapped_loss(pipeline_loss_fn(cfg, geom, sd, pod_axis=None),
                         mesh, pspecs, bspecs)
        f2 = mapped_loss(pipeline_loss_fn(cfg2, geom2, sd2, pod_axis=None),
                         mesh2, pspecs2, bspecs2)
        l1, n1 = f1(params, batch)
        l2, n2 = f2(params2, batch2)
        assert float(n1) == float(n2), (n1, n2)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)

        def scalar(fn, b):
            def s(p):
                l, n = fn(p, b)
                return l / n
            return s
        g1 = jax.grad(scalar(f1, batch))(params)
        g2 = jax.grad(scalar(f2, batch2))(params2)
        # stage grads live in different stackings; compare unstacked
        u1 = unstack_stages(g1["stages"], cfg.spec.n_layers)
        u2 = unstack_stages(g2["stages"], cfg.spec.n_layers, v=2)
        for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        for name in ("embed", "final_norm"):
            np.testing.assert_allclose(np.asarray(g1[name]),
                                       np.asarray(g2[name]),
                                       rtol=1e-6, atol=1e-7)
        print("OK interleaved v2", float(l2))
    """)
