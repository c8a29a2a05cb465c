"""RLHF PPO starter: per-role engine (actor/critic), KV-cache rollout
generation, clipped-PPO updates.

Run (CPU CI or real chips):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/rlhf_ppo.py --rounds 2

The toy reward prefers responses ending in even tokens — watch
mean_reward climb while mean_kl stays bounded by the KL penalty
against the frozen reference policy.
"""

import argparse
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt_len", type=int, default=4)
    p.add_argument("--max_new", type=int, default=8)
    # serve generation from a dedicated process (the reference's
    # vLLM-engine topology): weights ship over the shm substrate
    p.add_argument("--cross_process", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import (
        LlamaConfig,
        forward,
        init_params,
        param_logical_axes,
    )
    from dlrover_tpu.rl.config import RLConfig
    from dlrover_tpu.rl.engine import ModelEngine
    from dlrover_tpu.rl.inference import KVCacheBackend
    from dlrover_tpu.rl.trainer import (
        RLHFTrainer,
        actor_ppo_loss,
        critic_value_loss,
    )

    cfg = LlamaConfig.tiny(remat="none")
    n = len(jax.devices())
    config = RLConfig.from_dict(
        {
            "roles": {
                "actor": {"strategy": {"data": n, "remat": "none"}},
                "critic": {"strategy": {"data": n, "remat": "none"}},
            },
            "ppo": {"rollout_batch": args.batch, "ppo_epochs": 1},
        }
    )

    def actor_forward(params, tokens):
        return forward(params, tokens, cfg)

    engine = ModelEngine(config)
    engine.build_role(
        "actor",
        loss_fn=lambda p, b: actor_ppo_loss(
            actor_forward(p, b["tokens"]), b
        ),
        optimizer=optax.adam(1e-4),
        init_params_fn=lambda rng: init_params(rng, cfg),
        param_axes=param_logical_axes(cfg),
    )

    def critic_init(rng):
        return {
            "emb": jax.random.normal(
                rng, (cfg.vocab_size, 16), jnp.float32
            )
            * 0.1,
            "w": jnp.zeros((16,), jnp.float32),
        }

    def critic_value(p, tokens):
        return jnp.einsum("bse,e->bs", p["emb"][tokens], p["w"])

    engine.build_role(
        "critic",
        loss_fn=lambda p, b: critic_value_loss(
            critic_value(p, b["tokens"]), b
        ),
        optimizer=optax.adam(1e-3),
        init_params_fn=critic_init,
        param_axes={"emb": (None, None), "w": (None,)},
    )
    engine.init_role_state("actor", jax.random.PRNGKey(0))
    engine.init_role_state("critic", jax.random.PRNGKey(1))

    if args.cross_process:
        # generation in a SEPARATE process: each policy update is
        # published through shared memory and resharded onto the
        # worker's inference layout (rl/generation_service.py; ref
        # vllm_backend.py) — no in-process pointer sharing
        import dataclasses

        from dlrover_tpu.rl.generation_service import ServingEngine

        backend = ServingEngine(
            factory=(
                "dlrover_tpu.rl.generation_service:"
                "tiny_llama_factory"
            ),
            # the spec crosses a process boundary as JSON — ship only
            # the primitive config fields (dtype stays the default)
            factory_kwargs={
                k: v
                for k, v in dataclasses.asdict(cfg).items()
                if isinstance(v, (int, float, str, bool))
            },
            max_new_tokens=args.max_new,
            num_replicas=1,
            max_slots=args.batch,
            max_seq_len=args.prompt_len + args.max_new,
        )
    else:
        backend = KVCacheBackend(cfg, max_new_tokens=args.max_new)

    trainer = RLHFTrainer(
        config,
        engine,
        backend,
        actor_forward=actor_forward,
        critic_value=critic_value,
        reward_fn=lambda tokens: np.asarray(
            (np.asarray(tokens)[:, -1] % 2 == 0), np.float32
        ),
        prompt_len=args.prompt_len,
    )
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(
            0, cfg.vocab_size, (args.batch, args.prompt_len)
        ).astype(np.int32)
        for _ in range(args.rounds)
    ]
    history = trainer.train(prompts, jax.random.PRNGKey(2))
    for i, h in enumerate(history):
        print(
            f"round {i}: reward {h['mean_reward']:.3f} "
            f"kl {h['mean_kl']:.4f} actor_loss {h['actor_loss']:.4f}",
            flush=True,
        )
    if args.cross_process:
        s = backend.status()
        print(
            f"generation service: {s['completed']} rollouts, "
            f"{args.max_new / max(s['p50_latency_s'], 1e-9):.1f} tok/s "
            f"a rollout at the median, weight handoff: publish "
            f"{backend.publish_s * 1e3:.1f} ms, "
            f"policy version {s['version']}",
            flush=True,
        )
        backend.close()


if __name__ == "__main__":
    main()
