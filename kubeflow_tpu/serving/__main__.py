"""Serving server CLI: `python -m kubeflow_tpu.serving`.

The deployable entry point the ModelServer controller's pods run —
and the standalone way to stand the REST server up from a train
checkpoint (the reference's analog was the removed TF-Serving binary,
`/root/reference/docs_dev/tf_serving.md:1-60`).

    python -m kubeflow_tpu.serving --model llama-tiny --random --port 8000
    python -m kubeflow_tpu.serving --model llama3-1b \
        --checkpoint /ckpt/run7 --continuous --warmup --quant int8

--checkpoint points at a train.Checkpointer directory (Orbax OCDBT);
the latest step's params are restored (optimizer state is skipped).
--random initializes fresh params — the smoke/dev path that lets the
controller's e2e run without weights.
"""

from __future__ import annotations

import argparse
import os
import sys


def model_registry():
    """name -> (config, init_fn, family). (Importing this module pulls
    jax regardless — the serving package __init__ imports the engine —
    which is why the ModelServer CONTROLLER mirrors MODEL_NAMES as a
    literal instead of importing it; tests pin the two together.)"""
    from kubeflow_tpu.models import gemma, granite_hybrid, llama, llama_moe
    from kubeflow_tpu.serving.engine import (
        GEMMA_FAMILY, LLAMA_FAMILY, MOE_LLAMA_FAMILY, granite_hybrid_family,
    )

    def granite(cfg):
        return cfg, granite_hybrid.init, granite_hybrid_family(cfg)

    return {
        "llama-tiny": (llama.LLAMA_TINY, llama.init, LLAMA_FAMILY),
        "llama3-1b": (llama.LLAMA3_1B, llama.init, LLAMA_FAMILY),
        "llama3-8b": (llama.LLAMA3_8B, llama.init, LLAMA_FAMILY),
        "gemma-tiny": (gemma.GEMMA_TINY, gemma.init, GEMMA_FAMILY),
        "gemma-2b": (gemma.GEMMA_2B, gemma.init, GEMMA_FAMILY),
        "mixtral-tiny": (llama_moe.MIXTRAL_TINY, llama_moe.init,
                         MOE_LLAMA_FAMILY),
        # Mamba-2 layers with a position-free GQA layer every tenth:
        # continuous batching only (--continuous), a recurrent state
        # per slot beside the paged pool
        "granite-hybrid-tiny": granite(granite_hybrid.GRANITE_HYBRID_TINY),
        "granite-4.0-h-micro": granite(granite_hybrid.GRANITE_4_0_H_MICRO),
    }


MODEL_NAMES = tuple(model_registry())


def _load_params(args, init1):
    """`init1` is a rng-only closure over (init_fn, cfg)."""
    import jax

    if args.random:
        return init1(jax.random.key(args.seed))
    import orbax.checkpoint as ocp

    from kubeflow_tpu.train.checkpoint import STATE_ITEM

    mgr = ocp.CheckpointManager(args.checkpoint,
                                item_names=(STATE_ITEM,))
    step = mgr.latest_step()
    if step is None:
        raise SystemExit(f"no checkpoint under {args.checkpoint}")
    abstract = jax.eval_shape(
        init1, jax.ShapeDtypeStruct((2,), "uint32"))
    # partial restore: serving wants the params SUBTREE only — pulling
    # the Adam moments (2x params) through disk and HBM to throw away
    # would double a large model's startup IO
    restored = mgr.restore(step, args=ocp.args.Composite(**{
        STATE_ITEM: ocp.args.PyTreeRestore(
            {"params": abstract}, partial_restore=True),
    }))
    mgr.close()
    return restored[STATE_ITEM]["params"]


def _make_reloader(init_fn, cfg, quant: str):
    """Build the /v1/reload weight materializer for this process: a
    checkpoint source goes through the same Orbax partial-restore path
    as boot (plus the boot-time quantization, so a reload can't
    silently de-quantize a server started with --quant); a seed source
    (`{"seed": N}`) re-initializes — the loadtest/chaos path that
    needs distinguishable weights without writing checkpoints."""
    def _reload(name, engine, source):
        import jax

        if "seed" in source:
            params = init_fn(jax.random.key(int(source["seed"])), cfg)
        else:
            ckpt_dir = source.get("checkpoint", "")
            if not ckpt_dir:
                raise ValueError(
                    "reload source needs 'checkpoint' or 'seed'")
            import orbax.checkpoint as ocp

            from kubeflow_tpu.train.checkpoint import STATE_ITEM

            # boot's _load_params shape: abstract from init_fn (NOT
            # engine.params, which may be int8-quantized already),
            # params subtree only, pinned to source["step"] when given
            mgr = ocp.CheckpointManager(ckpt_dir,
                                        item_names=(STATE_ITEM,))
            try:
                step = source.get("step")
                if not isinstance(step, int):
                    step = mgr.latest_step()
                if step is None:
                    raise ValueError(
                        f"no checkpoint under {ckpt_dir!r}")
                abstract = jax.eval_shape(
                    lambda k: init_fn(k, cfg),
                    jax.ShapeDtypeStruct((2,), "uint32"))
                restored = mgr.restore(
                    step, args=ocp.args.Composite(**{
                        STATE_ITEM: ocp.args.PyTreeRestore(
                            {"params": abstract},
                            partial_restore=True),
                    }))
            finally:
                mgr.close()
            params = restored[STATE_ITEM]["params"]
        if quant == "int8":
            from kubeflow_tpu.serving.quant import quantize_blocks

            params = quantize_blocks(params)
        return params

    return _reload


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m kubeflow_tpu.serving")
    p.add_argument("--model", default="llama-tiny", choices=MODEL_NAMES)
    p.add_argument("--name", default="",
                   help="served model name (default: --model)")
    src = p.add_mutually_exclusive_group()
    src.add_argument("--checkpoint", default="",
                     help="train.Checkpointer directory")
    src.add_argument("--random", action="store_true",
                     help="fresh random params (smoke/dev)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--max-len", type=int, default=1024)
    p.add_argument("--eos", type=int, default=None)
    p.add_argument("--continuous", action="store_true")
    p.add_argument("--warmup", action="store_true")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--batch-window-ms", type=float, default=0.0)
    p.add_argument("--prefill-chunk-tokens", type=int, default=0,
                   help="chunked prefill token budget (continuous "
                        "only): admission prefill feeds at most this "
                        "many prompt tokens per worker iteration, "
                        "interleaved with decode chunks — bounds the "
                        "decode stall a long prompt imposes. 0 = "
                        "the default (256)")
    p.add_argument("--kv-spill-bytes", type=int, default=0,
                   help="host-RAM KV spill tier byte budget "
                        "(continuous only): radix eviction demotes "
                        "block contents to host memory instead of "
                        "discarding, and a returning prefix restores "
                        "them with a host->device copy instead of "
                        "recomputing prefill. Size from the "
                        "reuse-distance histogram's mass beyond the "
                        "pool (docs/operator-guide.md). 0 = off")
    p.add_argument("--spec-decode", action="store_true",
                   help="speculative decoding on the paged KV cache "
                        "(continuous only): every request drafts "
                        "--spec-gamma tokens with --draft-model and "
                        "verifies them in one fused batched pass — "
                        "token-identical to plain decode")
    p.add_argument("--draft-model", default="",
                   choices=("",) + MODEL_NAMES,
                   help="draft model for --spec-decode (must share "
                        "the target's vocab)")
    p.add_argument("--draft-checkpoint", default="",
                   help="train.Checkpointer directory for the draft "
                        "params (default: random init — smoke/dev)")
    p.add_argument("--spec-gamma", type=int, default=4,
                   help="draft tokens proposed per speculative round")
    p.add_argument("--pipeline-depth", type=int, default=0,
                   help="decode dispatch-ahead depth (0 = backend-"
                        "aware default: 2 on TPU, 1 elsewhere)")
    p.add_argument("--paged-attention-impl", default="auto",
                   choices=("auto", "xla", "pallas"),
                   help="decode attention over the paged KV pool "
                        "(continuous only): xla gathers each row's "
                        "full window through the block table, pallas "
                        "walks the table in-kernel (TPU only), auto = "
                        "pallas on TPU, xla elsewhere")
    p.add_argument("--quant", choices=("", "int8"), default="")
    p.add_argument("--tokenizer", default="",
                   help="data.bpe tokenizer file (text mode); 'auto' "
                        "uses tokenizer.json beside --checkpoint when "
                        "present (tools/prepare_data.py's output name), "
                        "byte fallback otherwise")
    p.add_argument("--cpu", action="store_true",
                   help="pin the CPU backend (hermetic smoke) — the one "
                        "explicit way to serve without the accelerator")
    p.add_argument("--drain-grace-s", type=float, default=30.0,
                   help="shutdown waits this long for in-flight "
                        "generations before closing")
    p.add_argument("--tenants", default="",
                   help="tenancy config JSON file (continuous only): "
                        "per-tenant weights, priorities, rate limits, "
                        "KV shares — see kubeflow_tpu.tenancy. "
                        "Requests select a tenant with the X-Tenant "
                        "header; absent/unknown maps to 'default'")
    p.add_argument("--pool", default="mixed",
                   choices=("mixed", "prefill", "decode"),
                   help="disaggregation role (continuous only for "
                        "prefill/decode): 'prefill' replicas serve "
                        ":prefill handoffs and ship KV blocks to the "
                        "decode pool; 'decode' replicas receive them; "
                        "'mixed' serves both phases (default)")
    p.add_argument("--fleet-router", default="",
                   help="fleet router base URL; the replica registers "
                        "and heartbeats there (kubeflow_tpu.fleet)")
    p.add_argument("--advertise", default="",
                   help="URL the fleet router should reach this "
                        "replica at (default http://HOST:PORT)")
    p.add_argument("--model-version", default="",
                   help="model version label this replica boots with "
                        "(rides in fleet heartbeats; POST /v1/reload "
                        "updates it live — the rollout plane's "
                        "confirmation signal, ISSUE 18)")
    args = p.parse_args(argv)
    if not args.checkpoint and not args.random:
        p.error("pass --checkpoint DIR or --random")
    if args.warmup and not args.continuous:
        # create_serving_app only wires warmup for the continuous
        # batcher; silently ignoring the flag would break the "Ready
        # means compiled" promise
        p.error("--warmup requires --continuous")
    if args.paged_attention_impl != "auto" and not args.continuous:
        p.error("--paged-attention-impl requires --continuous")
    if args.prefill_chunk_tokens and not args.continuous:
        p.error("--prefill-chunk-tokens requires --continuous")
    if args.kv_spill_bytes and not args.continuous:
        # the spill tier hangs off the continuous batcher's block
        # pool; silently ignoring the budget would serve with the
        # recompute-on-evict behavior the operator paid RAM to avoid
        p.error("--kv-spill-bytes requires --continuous")
    if args.spec_decode and not args.continuous:
        p.error("--spec-decode requires --continuous")
    if args.spec_decode and not args.draft_model:
        p.error("--spec-decode requires --draft-model")
    if args.draft_model and not args.spec_decode:
        p.error("--draft-model requires --spec-decode")
    if args.tenants and not args.continuous:
        # the QoS scheduler replaces the CONTINUOUS batcher's queue;
        # silently ignoring the file would serve without the quotas
        # the operator configured
        p.error("--tenants requires --continuous")
    if args.advertise and not args.fleet_router:
        p.error("--advertise requires --fleet-router")
    if args.pool != "mixed" and not args.continuous:
        # the handoff path ships paged KV blocks, which only the
        # continuous engine has
        p.error("--pool prefill/decode requires --continuous")

    import jax

    from kubeflow_tpu import compile_cache
    from kubeflow_tpu.utils import device_stamp

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    compile_cache.enable()

    from aiohttp import web

    from kubeflow_tpu.serving.continuous import PREFILL_CHUNK_TOKENS
    from kubeflow_tpu.serving.engine import EngineConfig, InferenceEngine
    from kubeflow_tpu.serving.server import (
        create_serving_app,
        enable_fleet_registration,
    )

    cfg, init_fn, family = model_registry()[args.model]
    if family.recurrent and not args.continuous:
        raise SystemExit(
            f"{args.model} has recurrent layers: it is served by the "
            "continuous batcher alone (--continuous), which keeps a "
            "state per slot beside the paged pool")
    params = _load_params(args, lambda k: init_fn(k, cfg))
    if args.quant == "int8":
        from kubeflow_tpu.serving.quant import quantize_blocks

        params = quantize_blocks(params)
    engine = InferenceEngine(
        params, cfg, family,
        EngineConfig(max_len=args.max_len, eos_token=args.eos))
    tokenizer = None
    tok_ref = args.tokenizer
    if tok_ref == "auto":
        # The prepare_data -> train -> serve loop drops its tokenizer
        # at the last hop unless someone carries it: prefer the trained
        # tokenizer saved beside the checkpoint over the byte fallback.
        tok_ref = ""
        if args.checkpoint:
            from etils import epath

            cand = epath.Path(args.checkpoint) / "tokenizer.json"
            if cand.exists():
                tok_ref = str(cand)
    if tok_ref:
        from etils import epath

        from kubeflow_tpu.data.bpe import Tokenizer

        # epath, not open(): the checkpoint (and its tokenizer) can
        # live on gs:// — same reasoning as train/checkpoint.py's
        # data-state probe.
        tokenizer = Tokenizer.loads(epath.Path(tok_ref).read_text())
    tenancy = None
    if args.tenants:
        from kubeflow_tpu.tenancy import load_config

        tenancy = load_config(args.tenants)
    name = args.name or args.model
    drafts = None
    if args.spec_decode:
        dcfg, dinit, dfamily = model_registry()[args.draft_model]
        if args.draft_checkpoint:
            dargs = argparse.Namespace(
                random=False, seed=args.seed,
                checkpoint=args.draft_checkpoint)
            dparams = _load_params(dargs, lambda k: dinit(k, dcfg))
        else:
            # random draft: proposals are junk (low acceptance) but the
            # plumbing — and token parity — is exactly production's
            dparams = dinit(jax.random.key(args.seed + 1), dcfg)
        # draft must cover the target's sequence space: verify appends
        # through the SAME cursor positions
        drafts = {name: InferenceEngine(
            dparams, dcfg, dfamily,
            EngineConfig(max_len=args.max_len, eos_token=args.eos))}
    app = create_serving_app(
        {name: engine},
        tokenizer=tokenizer,
        batch_window_ms=args.batch_window_ms,
        max_batch=args.max_batch,
        continuous=args.continuous,
        warmup=args.warmup,
        prefill_chunk_tokens=(args.prefill_chunk_tokens
                              or PREFILL_CHUNK_TOKENS),
        kv_spill_bytes=args.kv_spill_bytes or None,
        pipeline_depth=args.pipeline_depth or None,
        paged_attention_impl=args.paged_attention_impl,
        drafts=drafts,
        spec_decode=args.spec_decode,
        spec_gamma=args.spec_gamma,
        drain_grace_s=args.drain_grace_s,
        tenancy=tenancy,
        pool=args.pool,
        model_version=args.model_version,
        reloader=_make_reloader(init_fn, cfg, args.quant),
    )
    if args.fleet_router:
        enable_fleet_registration(
            app, args.fleet_router,
            args.advertise or f"http://{args.host}:{args.port}")
    # The device as JAX reports it: a server that came up on the wrong
    # backend says so on its first line (--cpu is the one way to ask
    # for the CPU; nothing here falls back to it).
    device = device_stamp()
    print(f"serving {args.name or args.model} "
          f"({'random' if args.random else args.checkpoint}) on "
          f"{args.host}:{args.port} backend={device['platform']} "
          f"device_kind={device['kind']!r} "
          f"devices={device['count']} jax={jax.__version__} "
          f"tokenizer={tok_ref or 'byte'}",
          flush=True)
    web.run_app(app, host=args.host, port=args.port, print=None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
