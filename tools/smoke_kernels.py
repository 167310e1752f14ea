#!/usr/bin/env python
"""chip_smoke.py's kernels phase: each of the four Pallas kernels,
compiled (`interpret=False`) at the `llama3-1b` head shapes, against
`ops.attention._xla_attention` reached directly — never through
`impl="auto"`, which on TPU would compare a kernel with itself.

  paged_attention   s = 1 over a 2048-cell block table; and the call
                    `mistral-7b.steady` makes (PERF.md): 16 rows of 32
                    query heads, 64-block tables over a five-rank pool
                    of 1025 blocks, contexts spread from 30 to 2400
                    tokens and two idle rows, a layer other than 0;
                    that one is also timed, many calls in one program,
                    and the line gives a call's microseconds
  prefill_append    s = 5 and s = 256 (one serving prefill chunk)
                    both also as the serving engines call them: the
                    pool as layer 1 of a two-layer array and the layer
                    as an operand (the squeezed rank-5 BlockSpec, the
                    alias over the whole array)
  decode_attention  s = 1 over a 2048-cell dense cache
  flash_attention   forward and backward at seq 2048

The reference runs under `default_matmul_precision("highest")`: XLA's
default fp32 matmul on TPU is a single bf16 pass, which would make the
oracle the less exact side. Inputs and outputs are bf16, the serving
and training dtype, which keeps 8 bits: the stated tolerance is 4 x 2^-8
of the reference's largest magnitude (the backward sums per-head
gradients that were each already rounded). A wrong mask, cursor or head
mapping is off by the magnitude itself.

Convention for a row whose visible set is empty (pad holes cover its
whole causal prefix): the kernels return exact zeros, the XLA path
returns the mean of V (its softmax over all-NEG_INF logits is uniform).
No caller reads such a row — they are host-masked filler — so neither
is "right"; this phase plants one such row in each single-token case,
asserts the kernel's zeros, and leaves that row out of the comparison
on purpose.

Prints one line per kernel, then one JSON object as its last line.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeflow_tpu import compile_cache  # noqa: E402

BF16_EPS = 2.0 ** -8


# Contexts of the rows of `mistral-7b.steady`'s decode step, in tokens
# of a 4096-cell table: 14 rows decoding at a mean of 711 (the traced
# window's: 13.6 rows at ~660) and two idle rows at cursor 0.
STEADY_CONTEXTS = (30, 90, 150, 250, 330, 420, 500, 580, 660, 800, 950,
                   1200, 1600, 2400, 1, 1)


def run(*, n_q: int, n_kv: int, hd: int, block_size: int, cells: int,
        chunk: int, flash_seq: int, interpret: bool,
        steady: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops import attention
    from kubeflow_tpu.ops.pallas.decode_attention import decode_attention
    from kubeflow_tpu.ops.pallas.flash_attention import flash_attention
    from kubeflow_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )
    from kubeflow_tpu.ops.pallas.prefill_append import (
        paged_prefill_append,
        vmem_bytes,
    )
    from kubeflow_tpu.utils import device_stamp

    device = device_stamp()
    print(f"kernels: jax={jax.__version__} device={device}", flush=True)

    dt = jnp.bfloat16
    rng = np.random.default_rng(0)
    nb = cells // block_size              # blocks per slot

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), dt)

    def reference(q, k, v, q_pos, kv_mask):
        with jax.default_matmul_precision("highest"):
            kv_pos = jnp.broadcast_to(
                jnp.arange(k.shape[1], dtype=jnp.int32)[None],
                (k.shape[0], k.shape[1]))
            return attention._xla_attention(
                q, k, v, q_pos, kv_pos, causal=True, kv_mask=kv_mask)

    results: dict[str, dict] = {}

    def check(name, got, ref, seconds, keep=None):
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        if keep is not None:
            got, ref = got[keep], ref[keep]
        tol = 4 * BF16_EPS * max(1.0, float(np.abs(ref).max()))
        err = float(np.abs(got - ref).max())
        ok = bool(np.isfinite(got).all() and err <= tol)
        results[name] = {"max_abs_err": round(err, 6),
                         "tolerance": round(tol, 6), "ok": ok,
                         "first_call_seconds": round(seconds, 2)}
        print(f"kernels: {name}: max_abs_err={err:.6f} "
              f"tolerance={tol:.6f} {'ok' if ok else 'FAIL'} "
              f"(first call {seconds:.1f}s)", flush=True)

    def timed(fn, *args, **kwargs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        return out, time.perf_counter() - t0

    # --- single-token cases: 8 rows, cursors from the first cell to the
    # last; row 1 has left-pad holes, row 2 is the empty-visible-set row
    b = 8
    cursors = np.array([0, block_size, 5, cells // 4 + 3, cells // 2,
                        cells - block_size - 1, cells - 2, cells - 1],
                       np.int32)
    mask = np.ones((b, cells), bool)
    mask[1, :block_size // 2] = False
    mask[2, :] = False
    keep = np.arange(b) != 2
    q1 = normal(b, 1, n_q, hd)
    pos = jnp.asarray(cursors)
    jmask = jnp.asarray(mask)

    # paged: every row owns a disjoint chain of pool blocks (block 0 is
    # the trash block), in scrambled physical order
    num_blocks = 1 + b * nb
    k_pool, v_pool = (normal(num_blocks, block_size, n_kv, hd)
                      for _ in range(2))
    table = jnp.asarray(
        1 + rng.permutation(b * nb).reshape(b, nb), jnp.int32)
    out, secs = timed(jax.jit(
        lambda q, kp, vp, t, p, m: paged_decode_attention(
            q, kp, vp, t, p, m, interpret=interpret)),
        q1, k_pool, v_pool, table, pos, jmask)
    k_rows = k_pool[table].reshape(b, cells, n_kv, hd)
    v_rows = v_pool[table].reshape(b, cells, n_kv, hd)
    ref = reference(q1, k_rows, v_rows, pos[:, None], jmask)
    check("paged_attention[s=1]", out, ref, secs, keep)
    empty_row_zero = not np.asarray(out, np.float32)[2].any()

    def layered(pool, layer):
        # `pool` as layer `layer` of layer + 1, the others other numbers
        return jnp.stack([normal(*pool.shape)] * layer + [pool])

    out, secs = timed(jax.jit(
        lambda q, kp, vp, t, p, m, layer: paged_decode_attention(
            q, kp, vp, t, p, m, layer=layer, interpret=interpret)),
        q1, layered(k_pool, 1), layered(v_pool, 1), table, pos, jmask,
        jnp.int32(1))
    check("paged_attention[s=1,layer=1 of 2]", out, ref, secs, keep)

    # the serving cell's call: its own rows, table and pool, layer 2 of
    # 3; then `calls` of them in one program, each on the next layer
    # and the last one's output, for a call's time on the device
    def steady_case(n_q, blocks_per_slot, num_blocks, calls, layers=3):
        width = blocks_per_slot * block_size
        ctx = np.maximum(
            np.asarray(STEADY_CONTEXTS) * width // 4096, 1)
        rows = len(ctx)
        live = (ctx - 1) // block_size + 1
        tab = np.zeros((rows, blocks_per_slot), np.int32)
        ids = 1 + rng.permutation(num_blocks - 1)[:live.sum()]
        for r, start in enumerate(np.cumsum(live) - live):
            tab[r, :live[r]] = ids[start:start + live[r]]
        # (drawn on the device: 400 MB of numpy normals take a minute)
        kp, vp = (jax.random.normal(
            key, (layers, num_blocks, block_size, n_kv, hd), dt)
            for key in jax.random.split(jax.random.key(0)))
        q, tab = normal(rows, 1, n_q, hd), jnp.asarray(tab)
        cur = jnp.asarray(ctx - 1, jnp.int32)
        valid = jnp.ones((rows, width), bool)    # an operand, as served
        call = jax.jit(lambda q, layer, valid: paged_decode_attention(
            q, kp, vp, tab, cur, valid, layer=layer, interpret=interpret))
        out, secs = timed(call, q, jnp.int32(layers - 1), valid)
        ref = reference(
            q, kp[layers - 1][tab].reshape(rows, width, n_kv, hd),
            vp[layers - 1][tab].reshape(rows, width, n_kv, hd),
            cur[:, None], None)
        name = (f"paged_attention[s=1,{rows}x{blocks_per_slot} blocks "
                f"of {num_blocks},layer={layers - 1} of {layers}]")
        check(name, out, ref, secs)

        def chained(q, valid):
            def one(q, i):
                return q + call(q, i % layers, valid) / 8, None
            return jax.lax.scan(one, q, jnp.arange(calls))[0]
        chained = jax.jit(chained)
        jax.block_until_ready(chained(q, valid))
        _, secs = timed(chained, q, valid)
        results[name]["call_us"] = round(secs / calls * 1e6, 1)
        results[name]["live_blocks"] = int(live.sum())
        print(f"kernels: {name}: {secs / calls * 1e6:.1f} us a call over "
              f"{calls} calls, {live.sum()} live blocks", flush=True)

    steady_case(**steady)

    # dense decode cache: the same rows, gathered
    out, secs = timed(jax.jit(
        lambda q, k, v, p, m: decode_attention(
            q, k, v, p, m, interpret=interpret)),
        q1, k_rows, v_rows, pos, jmask)
    check(f"decode_attention[{cells}]", out, ref, secs, keep)
    empty_row_zero &= not np.asarray(out, np.float32)[2].any()
    results["empty_visible_set_rows_are_zero"] = {"ok": bool(empty_row_zero)}
    print(f"kernels: empty-visible-set row -> zeros: {empty_row_zero}",
          flush=True)

    # --- prefill/append: s new tokens per row at its cursor; checks the
    # attention output of the valid tokens and the pool the call leaves
    # (block 0 aside: the XLA path parks padding tokens there, the
    # kernel writes nothing for them)
    def prefill_case(s, starts, lens, layer=None):
        g = len(starts)
        q, kn, vn = normal(g, s, n_q, hd), normal(g, s, n_kv, hd), \
            normal(g, s, n_kv, hd)
        tab = table[:g]
        qs, ql = jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32)
        m = jnp.ones((g, cells), bool)
        name = f"prefill_append[s={s}]"
        kp, vp = k_pool, v_pool
        if layer is not None:
            name = f"prefill_append[s={s},layer={layer} of {layer + 1}]"
            kp, vp = layered(k_pool, layer), layered(v_pool, layer)
        (out, kp2, vp2), secs = timed(jax.jit(
            lambda *a, layer: paged_prefill_append(
                *a, layer=layer, interpret=interpret)),
            q, kn, vn, kp, vp, tab, qs, ql, m,
            layer=None if layer is None else jnp.int32(layer))
        with jax.default_matmul_precision("highest"):
            ref, kp_ref, vp_ref = attention.paged_prefill_attention(
                q, kn, vn, k_pool, v_pool, tab, qs, ql, kv_mask=m,
                impl="xla")
        valid = np.arange(s)[None, :] < np.asarray(lens)[:, None]
        check(name, out, ref, secs, valid)
        if layer is not None:
            # the alias covers the whole array: the other layers come
            # back as they went in, to the bit
            same = all(np.array_equal(np.asarray(a[:layer]),
                                      np.asarray(b[:layer]))
                       for a, b in ((kp2, kp), (vp2, vp)))
            results[name + ".other_layers_untouched"] = {"ok": same}
            print(f"kernels: {name}: other layers untouched: {same}",
                  flush=True)
            kp2, vp2 = kp2[layer], vp2[layer]
        check(name + ".pool",
              jnp.stack([kp2[1:], vp2[1:]]),
              jnp.stack([kp_ref[1:], vp_ref[1:]]), 0.0)

    small = 5
    prefill_case(small,
                 [0, block_size - 2, block_size, 3 * block_size + 7,
                  cells // 2, cells - small, 11, cells // 3],
                 [small, small, small, 3, small, small, 1, 0])
    prefill_case(chunk, [0, cells - chunk - block_size // 2],
                 [chunk, chunk - 3])
    prefill_case(chunk, [block_size + 3, cells // 2], [chunk - 1, chunk],
                 layer=1)

    # --- flash: forward, and backward through a weighted sum
    fb = 2
    q, k, v, w = (normal(fb, flash_seq, n, hd)
                  for n in (n_q, n_kv, n_kv, n_q))
    fpos = jnp.broadcast_to(
        jnp.arange(flash_seq, dtype=jnp.int32)[None], (fb, flash_seq))

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, interpret=interpret)
        return (out.astype(jnp.float32) * w).sum(), out

    def ref_loss(q, k, v):
        out = reference(q, k, v, fpos, None)
        return (out.astype(jnp.float32) * w).sum(), out

    ((_, out), grads), secs = timed(jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)), q, k, v)
    (_, ref), ref_grads = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    check(f"flash_attention[{flash_seq}].fwd", out, ref, secs)
    for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        check(f"flash_attention[{flash_seq}].{name}", g, rg, 0.0)

    # what `auto` picks here, by the rules in ops/attention.py
    auto = {
        "paged_attention": attention.resolve_paged_attention_impl("auto"),
        **{f"paged_prefill_attention[s={s}]":
           attention.resolve_paged_prefill_impl(
               "auto", vmem_bytes=vmem_bytes(
                   s, n_q, n_kv, hd, block_size, 2))
           for s in (small, chunk, 4 * chunk)},
    }
    attention.reset_impl_counts()
    jax.eval_shape(lambda q, k, v: attention.dot_product_attention(
        q, k, v, pos[:, None], jnp.zeros((b, cells), jnp.int32),
        contiguous_positions=True), q1, k_rows, v_rows)
    jax.eval_shape(lambda q, k, v: attention.dot_product_attention(
        q, k, v, fpos, fpos, contiguous_positions=True), q, k, v)
    counts = attention.impl_counts()
    auto[f"dot_product_attention[s=1,{cells} cells]"] = (
        "decode" if counts["decode"] else "xla")
    auto[f"dot_product_attention[seq={flash_seq}]"] = (
        "flash" if counts["flash"] else "xla")
    print(f"kernels: auto selects {auto}", flush=True)

    failed = [k for k, r in results.items() if not r["ok"]]
    return {"ok": not failed, "problems": failed, "phase": "kernels",
            "jax": jax.__version__, "device": device,
            "kernels": results, "auto": auto}


def main() -> int:
    compile_cache.enable()
    # llama3-1b: 16 query heads over 8 KV heads of 128, 64-cell KV blocks,
    # --max-len 2048, --prefill-chunk-tokens 256
    # and mistral-7b.steady's decode call: 32 query heads, --max-len 4096
    result = run(n_q=16, n_kv=8, hd=128, block_size=64, cells=2048,
                 chunk=256, flash_seq=2048, interpret=False,
                 steady=dict(n_q=32, blocks_per_slot=64, num_blocks=1025,
                             calls=256))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
