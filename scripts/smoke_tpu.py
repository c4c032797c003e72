"""Chip smoke for the kernel layer: compile + run every Pallas kernel.

The CPU-mesh tests validate semantics under the Mosaic interpreter; this
script validates *Mosaic lowering and execution on hardware* — layouts,
iota ranks, VMEM staging, scalar-prefetch grids, and, on a host with more
than one chip, remote DMA, barrier semaphores and collective ids, which the
interpreter only simulates.

Two groups of legs, all in ONE process (a chip belongs to one process):

- the single-device kernels and the world-1 degenerate collectives (full
  kernel machinery, no wire traffic) on the first chip;
- with N > 1 chips, every overlapped collective over a mesh of ALL of them
  — ag_gemm (bf16 ring, int8 wire, bidirectional), gemm_rs, allgather
  (ring, bidirectional ring, full-mesh push), reduce-scatter, all-to-all,
  sp_gqa_decode — each compared with its XLA collective.

A wrong credit or barrier on hardware is a hang, not an exception, so each
leg prints its name BEFORE it starts and runs under its own timeout: a hang
names itself, and the process exits at once (the device is then wedged;
nothing after it could be trusted).  The summary line says which legs ran.

Run on the chip through the chip tool, from the repo root:

    python scripts/smoke_tpu.py

Off a TPU it refuses to start, the way ``chip_smoke.py`` does.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

# Seconds one leg may take, cold compile included, before it is a hang.
LEG_TIMEOUT_S = 180


def _shard1(fn, mesh, n_in, **kw):
    return jax.jit(jax.shard_map(
        functools.partial(fn, **kw), mesh=mesh,
        in_specs=(P("tp"),) * n_in, out_specs=P("tp"), check_vma=False))


def main():
    from triton_dist_tpu.runtime import configure_compile_cache, require_tpu
    from triton_dist_tpu.runtime.watchdog import (
        WatchdogTimeout,
        run_with_watchdog,
    )

    configure_compile_cache()
    require_tpu("scripts/smoke_tpu.py")
    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.array(devices[:1]), ("tp",))
    key = jax.random.key(0)
    results = []
    print(f"smoke_tpu: {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}; collective legs over {world} device(s)",
          flush=True)

    def _close(out, ref, tol):
        """Leaf-wise: exact for ``tol == 0`` (pure data movement), else
        max |out - ref| <= tol * max |ref| (bf16 matmuls whose block and
        ring orders differ)."""
        for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref),
                        strict=True):
            o, r = (np.asarray(o, np.float64), np.asarray(r, np.float64))
            if o.shape != r.shape:
                return f"shape {o.shape} vs reference {r.shape}"
            err = float(np.abs(o - r).max()) if o.size else 0.0
            bound = tol * max(float(np.abs(r).max()), 1e-30)
            if err > bound:
                return f"max err {err:.4g} > {bound:.4g}"
        return None

    def check(name, fn, ref=None, tol=0.0):
        """Run ``fn`` (and ``ref``) under the leg timeout; the outputs must
        be finite and, with ``ref``, match it."""
        print(f"{name:28s} ", end="", flush=True)  # a hang names itself

        def leg():
            out = jax.block_until_ready(fn())
            ok = all(np.isfinite(np.asarray(l, np.float32)).all()
                     for l in jax.tree.leaves(out)
                     if jnp.issubdtype(l.dtype, jnp.floating))
            if not ok:
                return "NONFINITE"
            if ref is not None:
                bad = _close(out, jax.block_until_ready(ref()), tol)
                if bad:
                    return f"MISMATCH vs XLA: {bad}"
            return "OK"

        try:
            verdict = run_with_watchdog(leg, LEG_TIMEOUT_S, name=name)
        except WatchdogTimeout:
            print(f"HANG (> {LEG_TIMEOUT_S} s)", flush=True)
            print(f"\nsmoke_tpu: {name} hung; "
                  f"{sum(r[1] == 'OK' for r in results)}/{len(results)} "
                  f"legs OK before it. Exiting without touching the "
                  f"device again.", flush=True)
            os._exit(3)
        except Exception as e:  # noqa: BLE001 — one leg's failure is a
            # table row; the exit code below still fails the run
            verdict = f"FAIL {type(e).__name__}: {str(e)[:200]}"
        results.append((name, verdict))
        print(verdict, flush=True)

    _single_device_legs(check, mesh, key)
    if world > 1:
        _collective_legs(check, devices, key)

    fails = [r for r in results if r[1] != "OK"]
    ran = " + ".join(
        ["single-device kernels", "world-1 collectives"]
        + ([f"world-{world} collectives vs XLA"] if world > 1 else []))
    if world == 1:
        ran += "; world-N collective legs: not run (one device)"
    print(f"\n{len(results) - len(fails)}/{len(results)} legs OK on "
          f"{jax.devices()[0].device_kind} x{len(jax.devices())} — legs "
          f"run: {ran}")
    return 1 if fails else 0


def _single_device_legs(check, mesh, key):
    """Every kernel on the first chip; collectives on their world-1
    degenerate path (the full kernel machinery, no wire traffic)."""
    # 1. base matmul (new 1024x1024x512 blocks)
    from triton_dist_tpu.kernels.gemm import matmul
    a = jax.random.normal(key, (2048, 2048), jnp.bfloat16)
    b = jax.random.normal(key, (2048, 1024), jnp.bfloat16)
    check("matmul", lambda: matmul(a, b))

    # 1b. int8 MXU matmul (double-rate path) — exactness vs numpy
    from triton_dist_tpu.kernels.quant import Int8MatmulConfig, matmul_i8
    rng = np.random.default_rng(0)
    ai = jnp.asarray(rng.integers(-127, 128, (512, 512), dtype=np.int8))
    bi = jnp.asarray(rng.integers(-127, 128, (512, 256), dtype=np.int8))

    def _i8():
        out = matmul_i8(ai, bi, config=Int8MatmulConfig(256, 256, 256))
        assert np.array_equal(np.asarray(out),
                              np.asarray(ai, np.int32) @ np.asarray(bi, np.int32))
        return out

    check("matmul_i8", _i8)

    # 2. grouped GEMM (scalar-prefetch grid)
    from triton_dist_tpu.kernels.group_gemm import group_gemm
    xs = jax.random.normal(key, (1024, 512), jnp.bfloat16)
    ws = jax.random.normal(key, (4, 512, 512), jnp.bfloat16)
    te = jnp.array([0, 1, 2, 3, 1, 2, 0, 3], jnp.int32)
    check("group_gemm",
          lambda: group_gemm(xs, ws, te, block_m=128, impl="pallas"))

    # 3. AG-GEMM world-1 (ring kernel, nested MXU pipeline)
    from triton_dist_tpu.kernels.allgather_gemm import ag_gemm_shard
    check("ag_gemm(w1)", lambda: _shard1(
        ag_gemm_shard, mesh, 2, axis="tp", impl="pallas",
        interpret=False)(a, b))

    # 3b. AG-GEMM world-1 int8 WIRE mode (aliased wire planes + dequant
    # at the MXU feed — r4)
    check("ag_gemm_wire(w1)", lambda: _shard1(
        ag_gemm_shard, mesh, 2, axis="tp", impl="pallas",
        wire_dtype="int8", interpret=False)(a, b))

    # 4. GEMM-RS world-1
    from triton_dist_tpu.kernels.gemm_reduce_scatter import gemm_rs_shard
    check("gemm_rs(w1)", lambda: _shard1(
        gemm_rs_shard, mesh, 2, axis="tp", impl="pallas",
        interpret=False)(a, b))

    # 5. allgather world-1 (full-mesh-push kernel)
    from triton_dist_tpu.kernels.allgather import (
        AllGatherMethod,
        _ag_pallas_shard,
    )
    x = jax.random.normal(key, (1024, 512), jnp.bfloat16)
    check("allgather(w1)", lambda: _shard1(
        _ag_pallas_shard, mesh, 1, axis="tp", world=1,
        method=AllGatherMethod.FULL_MESH_PUSH, interpret=False)(x))

    # 6. all_to_all world-1 (local-copy path)
    from triton_dist_tpu.kernels.all_to_all import fast_all_to_all_shard
    send = jax.random.normal(key, (1, 128, 512), jnp.bfloat16)
    splits = jnp.array([128], jnp.int32)
    check("all_to_all(w1)", lambda: _shard1(
        fast_all_to_all_shard, mesh, 2, axis="tp", impl="pallas",
        interpret=False)(send, splits))

    # 7. flash decode (local split-KV + combine)
    from triton_dist_tpu.kernels.flash_decode import gqa_decode_shard
    B, Hq, Hkv, hd, S = 4, 8, 2, 128, 1024
    q = jax.random.normal(key, (B, Hq, hd), jnp.bfloat16)
    kc = jax.random.normal(key, (B, Hkv, S, hd), jnp.bfloat16)
    vc = jax.random.normal(key, (B, Hkv, S, hd), jnp.bfloat16)
    lens = jnp.full((B,), S, jnp.int32)
    check("flash_decode", lambda: _shard1(
        gqa_decode_shard, mesh, 4, impl="pallas",
        interpret=False)(q, kc, vc, lens))

    # 7a'. windowed decode — the [2, B] lens prefetch layout (r5: the SP
    # window_lens plumbing) on hardware
    check("flash_decode_win", lambda: _shard1(
        gqa_decode_shard, mesh, 4, impl="pallas", interpret=False,
        window=300)(q, kc, vc, lens))

    # 7a''. multi-token (q_lens) verify decode — [3, B] lens layout +
    # T*G-row q block (r5)
    qm = jax.random.normal(key, (B, 4, Hq, hd), jnp.bfloat16)
    check("flash_decode_multitok", lambda: _shard1(
        gqa_decode_shard, mesh, 4, impl="pallas", interpret=False,
        q_lens=jnp.array([4, 3, 4, 2], jnp.int32))(qm, kc, vc, lens))

    # 7b. int8-KV decode kernel (lane-packed scale planes — r4)
    from triton_dist_tpu.kernels.flash_decode import quantize_kv
    kq8, ks8 = quantize_kv(kc.astype(jnp.float32))
    vq8, vs8 = quantize_kv(vc.astype(jnp.float32))
    check("flash_decode_i8", lambda: _shard1(
        gqa_decode_shard, mesh, 4, impl="pallas", interpret=False,
        k_scale=ks8, v_scale=vs8)(q, kq8, vq8, lens))

    # 7b'. paged decode (block_table by scalar prefetch; the kernel copies
    # each row's live pages in — r4, PR 25)
    from triton_dist_tpu.kernels.flash_decode import gqa_decode_paged_shard
    n_pages = S // 256
    pool_k = (kc.reshape(B, Hkv, n_pages, 256, hd)
              .transpose(0, 2, 1, 3, 4).reshape(B * n_pages, Hkv, 256, hd))
    pool_v = (vc.reshape(B, Hkv, n_pages, 256, hd)
              .transpose(0, 2, 1, 3, 4).reshape(B * n_pages, Hkv, 256, hd))
    tabl = jnp.arange(B * n_pages, dtype=jnp.int32).reshape(B, n_pages)
    check("paged_decode", lambda: _shard1(
        gqa_decode_paged_shard, mesh, 5, impl="pallas",
        interpret=False)(q, pool_k, pool_v, tabl, lens))

    # 7c. flash prefill (blockwise causal GQA, scalar-prefetch offsets)
    from triton_dist_tpu.kernels.flash_attention import flash_attention
    qp = jax.random.normal(key, (2, 8, 1024, 128), jnp.bfloat16)
    kp = jax.random.normal(key, (2, 2, 1024, 128), jnp.bfloat16)
    check("flash_prefill", lambda: jax.jit(functools.partial(
        flash_attention, causal=True, impl="pallas"))(qp, kp, kp))
    check("flash_prefill_off", lambda: jax.jit(functools.partial(
        flash_attention, causal=True, impl="pallas",
        return_lse=True))(qp[:, :, :128], kp, kp, q_offset=jnp.int32(512)))

    # 7c'. int8-KV flash prefill (scales fused in the block loop — r4)
    from triton_dist_tpu.kernels.flash_decode import quantize_kv as _qkv
    kp8, kps = _qkv(kp.astype(jnp.float32))
    check("flash_prefill_i8", lambda: jax.jit(functools.partial(
        flash_attention, causal=True, impl="pallas"))(
            qp, kp8, kp8, k_scale=kps, v_scale=kps))

    # 7c''. int8 scale-plane WHOLE-ARRAY escape (r5, ADVICE r4): bk == Sk
    # with (Sk//128) % 8 != 0 gives a [2, 128] f32 scale block — legal
    # only as a whole-array block, which interpret mode cannot validate.
    ks256 = jax.random.normal(key, (2, 2, 256, 128), jnp.float32)
    kq256, ksc256 = _qkv(ks256)
    q256 = jax.random.normal(key, (2, 4, 128, 128), jnp.bfloat16)
    check("flash_prefill_i8_smallS", lambda: jax.jit(functools.partial(
        flash_attention, causal=True, impl="pallas",
        q_offset=128))(q256, kq256, kq256, k_scale=ksc256,
                       v_scale=ksc256))

    # 7d. flash backward (dq + dkv kernels through the custom VJP)
    check("flash_bwd", lambda: jax.jit(jax.grad(
        lambda q_: jnp.sum(flash_attention(
            q_, kp, kp, causal=True, impl="pallas").astype(jnp.float32))))
        (qp))

    # 8. ring attention world-1 (pallas kernel, VMEM staging)
    from triton_dist_tpu.kernels.ring_attention import ring_attention_shard
    qr = jax.random.normal(key, (256, 2, 8, 128), jnp.bfloat16)
    kr = jax.random.normal(key, (256, 2, 2, 128), jnp.bfloat16)
    check("ring_attn(w1)", lambda: _shard1(
        ring_attention_shard, mesh, 3, axis="tp", causal=True,
        impl="pallas", interpret=False)(qr, kr, kr))

    # 8b. flash ring world-1 (r4: per-block flash + LSE merge) and its
    # gradient (the reverse flash ring over the bwd kernels)
    check("ring_flash(w1)", lambda: _shard1(
        ring_attention_shard, mesh, 3, axis="tp", causal=True,
        impl="flash", interpret=False)(qr, kr, kr))

    def _ring_flash_grad():
        fn = jax.jit(jax.shard_map(
            lambda q_, k_, v_: jax.grad(lambda qq: jnp.sum(
                ring_attention_shard(qq, k_, v_, axis="tp", causal=True,
                                     impl="flash", interpret=False)
                .astype(jnp.float32)))(q_),
            mesh=mesh, in_specs=(jax.sharding.PartitionSpec("tp"),) * 3,
            out_specs=jax.sharding.PartitionSpec("tp"), check_vma=False))
        return fn(qr, kr, kr)

    check("ring_flash_grad(w1)", _ring_flash_grad)

    # 8c. zigzag layout (r5): segmented per-block offset vectors through
    # the flash kernels (two position runs per shard) + the windowed twin
    check("ring_zigzag(w1)", lambda: _shard1(
        ring_attention_shard, mesh, 3, axis="tp", causal=True,
        impl="flash", interpret=False, zigzag=True)(qr, kr, kr))
    check("ring_zigzag_win(w1)", lambda: _shard1(
        ring_attention_shard, mesh, 3, axis="tp", causal=True,
        impl="flash", interpret=False, zigzag=True, window=100,
        soft_cap=30.0)(qr, kr, kr))

    def _ring_zigzag_grad():
        fn = jax.jit(jax.shard_map(
            lambda q_, k_, v_: jax.grad(lambda qq: jnp.sum(
                ring_attention_shard(qq, k_, v_, axis="tp", causal=True,
                                     impl="flash", interpret=False,
                                     zigzag=True)
                .astype(jnp.float32)))(q_),
            mesh=mesh, in_specs=(jax.sharding.PartitionSpec("tp"),) * 3,
            out_specs=jax.sharding.PartitionSpec("tp"), check_vma=False))
        return fn(qr, kr, kr)

    check("ring_zigzag_grad(w1)", _ring_zigzag_grad)

    # 9. ulysses world-1 (a2a + dense attention)
    from triton_dist_tpu.kernels.ulysses_attention import (
        ulysses_attention_shard)
    check("ulysses(w1)", lambda: _shard1(
        ulysses_attention_shard, mesh, 3, axis="tp", causal=True,
        impl="pallas", interpret=False)(qr, kr, kr))


def _collective_legs(check, devices, key):
    """Every overlapped collective over a mesh of all ``devices``, with
    real wire traffic, against the same host entry under ``impl="xla"``
    (the XLA collective)."""
    from triton_dist_tpu.kernels.all_to_all import (
        create_all_to_all_context,
        fast_all_to_all,
    )
    from triton_dist_tpu.kernels.allgather import (
        AllGatherMethod,
        all_gather,
        create_allgather_context,
    )
    from triton_dist_tpu.kernels.allgather_gemm import (
        ag_gemm_gathered,
        create_ag_gemm_context,
    )
    from triton_dist_tpu.kernels.flash_decode import (
        create_sp_decode_context,
        sp_gqa_decode,
    )
    from triton_dist_tpu.kernels.gemm_reduce_scatter import (
        create_gemm_rs_context,
        gemm_rs,
    )
    from triton_dist_tpu.kernels.reduce_scatter import (
        ReduceScatterMethod,
        create_reduce_scatter_context,
        reduce_scatter,
    )

    w = len(devices)
    mesh = Mesh(np.array(devices), ("tp",))
    tag = f"(w{w})"
    ks = jax.random.split(key, 8)
    m, k, n = 512 * w, 2048, 512 * w

    # AG-GEMM: A rows sharded, B columns sharded; (A_full, C) both checked
    a = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    b = jax.random.normal(ks[1], (k, n), jnp.bfloat16)
    for leg, kw in (("ag_gemm", {}),
                    ("ag_gemm_wire_i8", {"wire_dtype": "int8"}),
                    ("ag_gemm_bidir", {"ring_mode": "bidir"})):
        ctxs = {impl: create_ag_gemm_context(mesh, impl=impl, **kw)
                for impl in ("pallas", "xla")}
        check(leg + tag,
              lambda c=ctxs["pallas"]: ag_gemm_gathered(a, b, c),
              lambda c=ctxs["xla"]: ag_gemm_gathered(a, b, c), tol=2e-2)

    # GEMM-RS: A columns / B rows sharded on K, C rows scattered
    a2 = jax.random.normal(ks[2], (m, k), jnp.bfloat16)
    b2 = jax.random.normal(ks[3], (k, n // w), jnp.bfloat16)
    for leg, kw in (("gemm_rs", {}),
                    ("gemm_rs_bidir", {"ring_mode": "bidir"})):
        ctxs = {impl: create_gemm_rs_context(mesh, impl=impl, **kw)
                for impl in ("pallas", "xla")}
        check(leg + tag,
              lambda c=ctxs["pallas"]: gemm_rs(a2, b2, c),
              lambda c=ctxs["xla"]: gemm_rs(a2, b2, c), tol=3e-2)

    # allgather: pure data movement, bit-exact against lax.all_gather
    x = jax.random.normal(ks[4], (w * 512, 512), jnp.bfloat16)
    xla_ag = create_allgather_context(mesh, method=AllGatherMethod.XLA)
    for leg, method in (("allgather_ring", AllGatherMethod.RING_1D),
                        ("allgather_bidir", AllGatherMethod.RING_BIDIR),
                        ("allgather_push", AllGatherMethod.FULL_MESH_PUSH)):
        ctx = create_allgather_context(mesh, method=method)
        check(leg + tag, lambda c=ctx: all_gather(x, c),
              lambda: all_gather(x, xla_ag))

    # reduce-scatter: device i contributes the stacked partial x[i]
    parts = jax.random.normal(ks[5], (w, w * 256, 256), jnp.float32)
    xla_rs = create_reduce_scatter_context(
        mesh, method=ReduceScatterMethod.XLA)
    for leg, method in (("reduce_scatter_ring", ReduceScatterMethod.RING_1D),
                        ("reduce_scatter_bidir",
                         ReduceScatterMethod.RING_BIDIR)):
        ctx = create_reduce_scatter_context(mesh, method=method)
        check(leg + tag, lambda c=ctx: reduce_scatter(parts, c),
              lambda: reduce_scatter(parts, xla_rs), tol=1e-5)

    # all-to-all: rows past each segment's split are padding (the kernel
    # moves splits-proportional blocks), so only valid rows compare
    ep_mesh = Mesh(np.array(devices), ("ep",))
    max_tok, hidden = 128, 1024
    send = jax.random.normal(ks[6], (w * w, max_tok, hidden), jnp.bfloat16)
    splits = jax.random.randint(ks[7], (w * w,), 1, max_tok + 1, jnp.int32)

    def a2a(impl):
        ctx = create_all_to_all_context(ep_mesh, max_tok, hidden, impl=impl)
        recv, rsplits = fast_all_to_all(send, splits, ctx)
        valid = jnp.arange(max_tok)[None, :, None] < rsplits[:, None, None]
        return jnp.where(valid, recv, 0), rsplits

    check("all_to_all" + tag, lambda: a2a("pallas"), lambda: a2a("xla"))

    # SP flash-decode: KV sequence-sharded, per-rank split-KV partials,
    # comm-fused LSE combine (remote DMA of the partial planes)
    B, Hq, Hkv, hd = 4, 8, 2, 128
    S = w * 1024
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (B, Hkv, S, hd), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (B, Hkv, S, hd), jnp.bfloat16)
    lens = jnp.array([S, S // 2 + 3, 5, S - 1], jnp.int32)
    sp_mesh = Mesh(np.array(devices), ("sp",))
    ctxs = {impl: create_sp_decode_context(sp_mesh, impl=impl)
            for impl in ("pallas", "xla")}
    check("sp_gqa_decode" + tag,
          lambda: sp_gqa_decode(q, kc, vc, lens, ctxs["pallas"]),
          lambda: sp_gqa_decode(q, kc, vc, lens, ctxs["xla"]), tol=2e-2)


if __name__ == "__main__":
    sys.exit(main())
