#!/usr/bin/env python
"""Offline perplexity evaluation: KTSH shards → loss/ppl, one JSON line.

Reference parity: none — the reference has no training or evaluation
of any kind (SURVEY.md §2b); its closest analog is the TF-Serving
prediction-equality smoke check
(`/root/reference/testing/test_tf_serving.py:40-57`), whose serving
half here is the REST `:score` door.

The eval half of the data story (tokenize → shard → train → EVALUATE):
streams windows through the (native-or-fallback) loader, teacher-forces
them through the model, and reports the token-weighted mean NLL and
perplexity. Serving-side scoring of ad-hoc sequences is the REST
`:score` door; this tool is for whole-dataset numbers (val-loss
tracking, checkpoint comparison).

    python tools/eval_ppl.py --shards val.ktsh --model llama-tiny \
        --checkpoint /ckpt/run7 --batch 8 --seq 512
    python tools/eval_ppl.py --shards val.ktsh --model llama-tiny \
        --random --cpu    # plumbing check: ppl ~= vocab_size
"""

from __future__ import annotations

import argparse
import json
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeflow_tpu.serving.__main__ import MODEL_NAMES, model_registry  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--shards", required=True, nargs="+")
    p.add_argument("--model", default="llama-tiny", choices=MODEL_NAMES)
    src = p.add_mutually_exclusive_group()
    src.add_argument("--checkpoint", default="")
    src.add_argument("--random", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--max-batches", type=int, default=0,
                   help="0 = one full epoch")
    p.add_argument("--cpu", action="store_true",
                   help="pin the CPU backend")
    args = p.parse_args(argv)
    if not args.checkpoint and not args.random:
        p.error("pass --checkpoint DIR or --random")

    import jax

    from kubeflow_tpu import compile_cache

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    compile_cache.enable()

    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.data import loader as dl
    from kubeflow_tpu.serving.__main__ import _load_params
    from kubeflow_tpu.train.trainer import cross_entropy_loss

    cfg, init_fn, family = model_registry()[args.model]
    params = _load_params(args, lambda k: init_fn(k, cfg))

    # family-dispatched forward (the registry carries the module init;
    # apply lives beside it)
    from kubeflow_tpu.models import gemma, llama, llama_moe

    if family.name == "gemma":
        apply = lambda p_, t: gemma.apply(p_, cfg, t)        # noqa: E731
    elif family.name == "llama-moe":
        apply = lambda p_, t: llama_moe.apply(p_, cfg, t)[0]  # noqa: E731
    else:
        apply = lambda p_, t: llama.apply(p_, cfg, t)        # noqa: E731

    @jax.jit
    def nll(params, tokens, targets, mask):
        # token-weighted sums so ragged final batches average correctly
        loss = cross_entropy_loss(apply(params, tokens), targets, mask)
        w = jnp.sum(mask)
        return loss * w, w

    total, weight, batches = 0.0, 0.0, 0
    with dl.open_loader(args.shards, batch=args.batch, seq=args.seq,
                        seed=args.seed) as loader:
        per_epoch = (loader.n_windows // args.batch)
        n = args.max_batches or per_epoch
        for _ in range(min(n, per_epoch)):
            arr = jnp.asarray(loader.next_batch())
            mask = jnp.ones_like(arr[:, 1:], jnp.float32)
            s, w = nll(params, arr[:, :-1], arr[:, 1:], mask)
            total += float(s)
            weight += float(w)
            batches += 1
    if weight == 0:
        print("no tokens evaluated", file=sys.stderr)
        return 1
    loss = total / weight
    print(json.dumps({
        "metric": "eval_perplexity",
        "model": args.model,
        "source": args.checkpoint or "random",
        "loss": round(loss, 6),
        "ppl": round(float(np.exp(loss)), 4),
        "tokens": int(weight),
        "batches": batches,
        "backend": jax.default_backend(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
