"""Text generation from a checkpoint: HF weights (or random demo) ->
KV-cache decode backend.

    python examples/generate.py --max_new 32
    python examples/generate.py --model /path/to/llama-hf --prompt "1 2 3"
    python examples/generate.py --serve --replicas 2 --requests 8

With ``--model`` the prompt is tokenized with the checkpoint's
tokenizer when available; the demo path generates over random-token
prompts (the point is the decode machinery: prefill + cached
single-token steps under one jit).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")

import numpy as np


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="", help="HF checkpoint dir")
    p.add_argument("--prompt", default="")
    p.add_argument("--max_new", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument(
        "--serve", action="store_true",
        help="server mode: the continuous-batching multi-replica "
        "plane (rl/generation_service.ServingEngine)",
    )
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument(
        "--requests", type=int, default=8,
        help="demo request count in --serve mode",
    )
    return p.parse_args()


def serve_main(args) -> int:
    """``--serve`` quickstart: spin up the serving plane on the demo
    model, push a burst of mixed-length requests through it, print
    the tails + the serving pane.  This is the smallest end-to-end
    tour of the inference plane: paged-KV replicas, shm-ring
    transport, dispatcher, drain-safe completion."""
    import numpy as np

    from dlrover_tpu.rl.generation_service import ServingEngine

    cfg_kw = dict(
        vocab_size=512, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, mlp_dim=128, max_seq_len=128, remat="none",
    )
    engine = ServingEngine(
        factory="dlrover_tpu.rl.generation_service:tiny_llama_factory",
        max_new_tokens=args.max_new,
        temperature=args.temperature,
        factory_kwargs=cfg_kw,
        num_replicas=args.replicas,
        max_slots=8,
        block_size=16,
        num_blocks=256,
        max_seq_len=128,
        prefill_chunk=16,
    )
    try:
        rng = np.random.default_rng(0)
        ids = [
            engine.submit(
                rng.integers(
                    0, cfg_kw["vocab_size"],
                    (int(rng.integers(4, 17)),),
                ),
                seed=i,
            )
            for i in range(args.requests)
        ]
        for rid in ids:
            res = engine.result(rid)
            print(
                f"req {rid} [{res['finish_reason']}, replica "
                f"{res['replica']}, {res['latency_s']:.3f}s]: "
                + " ".join(map(str, res["tokens"].tolist()))
            )
        print("serving status:", engine.status())
    finally:
        engine.close()
    return 0


def main():
    args = parse_args()
    if args.serve:
        return serve_main(args)
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.rl.inference import KVCacheBackend

    tokenizer = None
    if args.model:
        import transformers

        from dlrover_tpu.models.hf_convert import params_from_hf

        model = transformers.LlamaForCausalLM.from_pretrained(
            args.model
        )
        params, cfg = params_from_hf(model)
        try:
            tokenizer = transformers.AutoTokenizer.from_pretrained(
                args.model
            )
        except OSError:
            pass
    else:
        from dlrover_tpu.models.llama import LlamaConfig, init_params

        cfg = LlamaConfig.tiny(vocab_size=512)
        params = init_params(jax.random.PRNGKey(0), cfg)

    backend = KVCacheBackend(
        cfg, max_new_tokens=args.max_new,
        temperature=args.temperature,
    )
    backend.sync_weights(params)

    if tokenizer is not None and args.prompt:
        ids = tokenizer(args.prompt, return_tensors="np").input_ids
        prompts = jnp.asarray(
            np.repeat(ids, args.batch, axis=0), jnp.int32
        )
    else:
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, 8), 0,
            cfg.vocab_size, dtype=jnp.int32,
        )

    out = backend.generate(prompts, jax.random.PRNGKey(2))
    out = np.asarray(out)
    for row in out:
        if tokenizer is not None:
            print(tokenizer.decode(row))
        else:
            print(" ".join(map(str, row.tolist())))


if __name__ == "__main__":
    main()
