"""Host-offloaded AdamW (optimizers/host_offload.py).

Reference parity: ``atorch/atorch/optimizers/adam_offload.py`` —
fp32 master/moments on the host, bucket-streamed updates.  Tests
check math parity against optax.adamw (fp32 trajectories), the
multi-chunk streaming path, in-place host-buffer reuse, and the
end-to-end offloaded train step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.optimizers.host_offload import (
    FusedOffloadState,
    HostOffloadAdamW,
    OffloadState,
    build_fused_offload_step,
    build_offloaded_train_step,
)


def _tree_params(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w": jax.random.normal(k1, (300,), jnp.float32),
        "b": jax.random.normal(k2, (7,), jnp.float32),
        "m": jax.random.normal(k3, (13, 11), jnp.float32),
    }


class TestMathParity:
    @pytest.mark.parametrize("chunk", [1 << 20, 128])
    def test_matches_optax_adamw(self, chunk):
        """Multi-step trajectory of the offloaded optimizer matches
        optax.adamw run in fp32 (same lr/betas/eps/wd).  chunk=128
        forces the multi-chunk path on every leaf."""
        lr, wd = 1e-2, 0.01
        params = _tree_params(jax.random.PRNGKey(0))
        opt = HostOffloadAdamW(
            learning_rate=lr, weight_decay=wd, chunk_elems=chunk
        )
        state = opt.init(params)
        ref_opt = optax.adamw(lr, weight_decay=wd)
        ref_params = jax.tree_util.tree_map(jnp.asarray, params)
        ref_state = ref_opt.init(ref_params)

        for i in range(5):
            # deterministic synthetic grads, fp32 on both sides
            grads = jax.tree_util.tree_map(
                lambda p: 0.1 * p + 0.01 * (i + 1), state.master
            )
            grads_dev = jax.tree_util.tree_map(jnp.asarray, grads)
            state = opt.apply_gradients(state, grads_dev)
            updates, ref_state = ref_opt.update(
                jax.tree_util.tree_map(jnp.asarray, grads),
                ref_state,
                ref_params,
            )
            ref_params = optax.apply_updates(ref_params, updates)
            # masters track the fp32 reference to float tolerance
            for a, b in zip(
                jax.tree_util.tree_leaves(state.master),
                jax.tree_util.tree_leaves(ref_params),
            ):
                # atol admits the CPU backend's fp32 contraction
                # ordering (measured ~3e-7 off the optax reference
                # there; exact on TPU)
                np.testing.assert_allclose(
                    a, np.asarray(b), rtol=2e-5, atol=5e-7
                )

    def test_device_params_are_bf16_of_master(self):
        opt = HostOffloadAdamW(learning_rate=1e-2)
        state = opt.init(_tree_params(jax.random.PRNGKey(1)))
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(0.5 * p), state.master
        )
        state = opt.apply_gradients(state, grads)
        for p, m in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(state.master),
        ):
            assert p.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(p, np.float32),
                m.astype(np.float32),
                rtol=1e-2,  # bf16 mantissa
            )


class TestHostResidency:
    def test_state_is_host_numpy_and_reused(self):
        """The fp32 state must be numpy (host DRAM, zero HBM) and the
        update must write the SAME buffers in place — reallocation
        would double host memory at 2B-param scale."""
        opt = HostOffloadAdamW(learning_rate=1e-2, chunk_elems=64)
        state = opt.init({"w": np.ones((500,), np.float32)})
        assert isinstance(state.master["w"], np.ndarray)
        assert isinstance(state.mu["w"], np.ndarray)
        buf_m = state.master["w"]
        buf_mu = state.mu["w"]
        state2 = opt.apply_gradients(
            state, {"w": jnp.ones((500,), jnp.float32)}
        )
        assert state2.master["w"] is buf_m  # in-place
        assert state2.mu["w"] is buf_mu
        assert not np.array_equal(buf_m, np.ones((500,)))  # updated
        assert state2.step == 1

    def test_checkpoint_roundtrip(self):
        """The state snapshots through device_get/asarray like any
        train state (flash-ckpt compatibility)."""
        opt = HostOffloadAdamW(learning_rate=1e-2)
        state = opt.init({"w": np.full((64,), 2.0, np.float32)})
        state = opt.apply_gradients(
            state, {"w": jnp.ones((64,), jnp.float32)}
        )
        snap = jax.tree_util.tree_map(
            np.asarray, state._asdict()
        )
        restored = OffloadState(
            step=int(snap["step"]) if not isinstance(
                snap["step"], int
            ) else snap["step"],
            params=jax.tree_util.tree_map(
                jnp.asarray, snap["params"]
            ),
            master=snap["master"],
            mu=snap["mu"],
            nu=snap["nu"],
        )
        s1 = opt.apply_gradients(
            state, {"w": jnp.ones((64,), jnp.float32)}
        )
        s2 = opt.apply_gradients(
            restored, {"w": jnp.ones((64,), jnp.float32)}
        )
        np.testing.assert_allclose(
            s1.master["w"], s2.master["w"], rtol=1e-7
        )


class TestOffloadedTrainStep:
    def test_end_to_end_converges(self):
        target = jnp.full((256,), 3.0)

        def loss_fn(params, batch):
            pred = params["w"].astype(jnp.float32) * batch["x"]
            return jnp.mean((pred - target) ** 2)

        init_state, train_step = build_offloaded_train_step(
            loss_fn,
            lambda rng: {
                "w": jax.random.normal(rng, (256,), jnp.float32)
            },
            HostOffloadAdamW(learning_rate=0.1, chunk_elems=100),
        )
        state = init_state(jax.random.PRNGKey(0))
        batch = {"x": jnp.ones((256,))}
        first = None
        for _ in range(60):
            state, metrics = train_step(state, batch)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < 0.05 * first
        assert state.step == 60


class TestGroupedOffload:
    """Two-group backward (build_grouped_offload_step): the ceiling
    lever past ~2B params.  Exactness is the whole point — the split
    must reproduce the single-backward chunked trajectory to float
    noise (same grads at the same step-start params, same AdamW)."""

    def test_matches_single_group_exactly(self):
        from dlrover_tpu.models.llama import (
            LlamaConfig,
            init_params,
            loss_fn,
            loss_fn_grouped,
        )
        from dlrover_tpu.optimizers.host_offload import (
            build_grouped_offload_step,
        )

        cfg = LlamaConfig.tiny(remat="none")
        params = init_params(jax.random.PRNGKey(0), cfg)
        boundary = 1
        part_a = {
            "embed": params["embed"],
            "layers": jax.tree_util.tree_map(
                lambda l: l[:boundary], params["layers"]
            ),
        }
        part_b = {
            "layers": jax.tree_util.tree_map(
                lambda l: l[boundary:], params["layers"]
            ),
            "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }
        kw = dict(learning_rate=0.01, chunk_elems=1000)
        init_g, step_g = build_grouped_offload_step(
            lambda a, b, batch: loss_fn_grouped(a, b, batch, cfg),
            lambda: part_a,
            lambda: part_b,
            HostOffloadAdamW(**kw),
            HostOffloadAdamW(**kw),
        )
        init_p, step_p = build_offloaded_train_step(
            lambda p, b: loss_fn(p, b, cfg),
            lambda rng: params,
            HostOffloadAdamW(backend="numpy", **kw),
            mode="chunked",
        )
        sg = init_g(None)
        sp = init_p(jax.random.PRNGKey(9))
        tokens = np.ones((4, 17), dtype=np.int32)
        tokens[:, ::3] = 5
        batch = {"tokens": jnp.asarray(tokens)}
        for _ in range(3):
            sg, mg = step_g(sg, batch)
            sp, mp = step_p(sp, batch)
        np.testing.assert_allclose(
            float(mg["loss"]), float(mp["loss"]), rtol=1e-5
        )
        sa, sb = sg
        # group A's first-layer masters == the plain run's layer 0
        np.testing.assert_allclose(
            np.asarray(sa.master["layers"]["wq"]),
            np.asarray(sp.master["layers"]["wq"][:boundary]),
            rtol=2e-4, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(sb.master["lm_head"]),
            np.asarray(sp.master["lm_head"]),
            rtol=2e-4, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(sb.master["layers"]["w_down"]),
            np.asarray(sp.master["layers"]["w_down"][boundary:]),
            rtol=2e-4, atol=2e-5,
        )

    def test_grouped_init_builds_disjoint_groups(self):
        from dlrover_tpu.models.llama import (
            LlamaConfig,
            init_grouped_params,
        )

        cfg = LlamaConfig.tiny(remat="none")
        init_a, init_b = init_grouped_params(
            jax.random.PRNGKey(1), cfg, boundary=1
        )
        a = init_a()
        b = init_b()
        assert set(a) == {"embed", "layers"}
        assert set(b) == {"layers", "final_norm", "lm_head"}
        assert a["layers"]["wq"].shape[0] == 1
        assert (
            b["layers"]["wq"].shape[0] == cfg.n_layers - 1
        )


def _split_llama_parts(params, boundaries, n_layers):
    """Slice one materialized llama tree into N-group parts along the
    stacked layer dim (the ``loss_fn_ngrouped`` layout)."""
    bounds = [0, *boundaries, n_layers]
    parts = []
    n = len(bounds) - 1
    for i, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        part = {
            "layers": jax.tree_util.tree_map(
                lambda l: l[lo:hi], params["layers"]
            )
        }
        if i == 0:
            part["embed"] = params["embed"]
        if i == n - 1:
            part["final_norm"] = params["final_norm"]
            part["lm_head"] = params["lm_head"]
        parts.append(part)
    return parts


_NGROUP_STEPS = 2


def _ngroup_problem():
    from dlrover_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny(n_layers=5, remat="none")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = np.ones((4, 17), dtype=np.int32)
    tokens[:, ::3] = 5
    return cfg, params, {"tokens": jnp.asarray(tokens)}


_NGROUP_REF_CACHE = {}


def _ngroup_reference():
    """Single-pass chunked AdamW trajectory on the shared problem,
    computed ONCE for every boundary parametrization (the reference
    does not depend on the split)."""
    if _NGROUP_REF_CACHE:
        return _NGROUP_REF_CACHE["ref"]
    from dlrover_tpu.models.llama import loss_fn

    cfg, params, batch = _ngroup_problem()
    init_p, step_p = build_offloaded_train_step(
        lambda p, b: loss_fn(p, b, cfg),
        lambda rng: params,
        HostOffloadAdamW(
            backend="numpy", learning_rate=0.01,
            weight_decay=0.01, chunk_elems=1000,
        ),
        mode="chunked",
    )
    sp = init_p(jax.random.PRNGKey(9))
    losses, masters = [], []
    for _ in range(_NGROUP_STEPS):
        sp, mp = step_p(sp, batch)
        losses.append(float(mp["loss"]))
        # masters are updated IN PLACE — snapshot per step
        masters.append(jax.tree_util.tree_map(np.copy, sp.master))
    _NGROUP_REF_CACHE["ref"] = (losses, masters)
    return losses, masters


class TestNGroupOffload:
    """N-group grouped backward: the generalization of the two-group
    ceiling lever.  The contract is unchanged — EXACT single-step
    AdamW with every group's grads taken at the step-start params —
    so any N must reproduce the single-pass chunked trajectory to
    float noise, odd (non-divisible) layer splits included."""

    # N ∈ {1, 2, 4} on a toy stacked model (sub-second compiles):
    # same grouped-step machinery, same per-layer split semantics.
    # (1, 2, 4) over 5 layers is an odd (non-divisible) split.
    @pytest.mark.parametrize("boundaries", [(), (2,), (1, 2, 4)])
    def test_matches_single_pass_reference_toy(self, boundaries):
        from dlrover_tpu.optimizers.host_offload import (
            build_grouped_offload_step,
        )

        L, d = 5, 32
        stack = (
            np.random.RandomState(0).randn(L, d).astype(np.float32)
        )
        target = jnp.asarray(
            np.random.RandomState(1).randn(d).astype(np.float32)
        )

        def loss_full(params, batch):
            pred = jnp.sum(
                jnp.tanh(params["w"].astype(jnp.float32)), axis=0
            ) * batch["x"]
            return jnp.mean((pred - target) ** 2)

        bounds = [0, *boundaries, L]
        parts = [
            {"w": stack[lo:hi]}
            for lo, hi in zip(bounds, bounds[1:])
        ]

        def loss_grouped(*args):
            group_parts, batch = args[:-1], args[-1]
            w = jnp.concatenate(
                [p["w"] for p in group_parts], axis=0
            )
            return loss_full({"w": w}, batch)

        # wd > 0 so the decay term's group routing is covered too
        kw = dict(
            learning_rate=0.01, weight_decay=0.01, chunk_elems=48
        )
        init_g, step_g = build_grouped_offload_step(
            loss_grouped,
            init_fns=[lambda p=p: p for p in parts],
            optimizers=[HostOffloadAdamW(**kw) for _ in parts],
        )
        init_p, step_p = build_offloaded_train_step(
            loss_full,
            lambda rng: {"w": stack},
            HostOffloadAdamW(backend="numpy", **kw),
            mode="chunked",
        )
        sg = init_g(None)
        sp = init_p(jax.random.PRNGKey(9))
        batch = {"x": jnp.ones((d,), jnp.float32)}
        for _ in range(3):
            sg, mg = step_g(sg, batch)
            sp, mp = step_p(sp, batch)
            # per-step check: the FIRST grouped step must already
            # match (no warm-up slack hiding a step-1 bug)
            np.testing.assert_allclose(
                float(mg["loss"]), float(mp["loss"]), rtol=1e-5
            )
        for i, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
            np.testing.assert_allclose(
                np.asarray(sg[i].master["w"]),
                sp.master["w"][lo:hi],
                rtol=2e-5, atol=2e-6,
            )

    # N=3 with the REAL llama grouped-loss structure (embed in group
    # 0, final_norm + lm_head in the last group), split (2, 3) = an
    # odd 2/1/2 segment layout; the legacy two-group llama test above
    # covers N=2 on the same structure
    def test_matches_single_pass_reference(self):
        boundaries = (2, 3)
        from dlrover_tpu.models.llama import loss_fn_ngrouped
        from dlrover_tpu.optimizers.host_offload import (
            build_grouped_offload_step,
        )

        cfg, params, batch = _ngroup_problem()
        ref_losses, ref_masters = _ngroup_reference()
        parts = _split_llama_parts(params, boundaries, cfg.n_layers)
        n = len(parts)
        # wd > 0 so the decay term's group routing is covered too
        kw = dict(
            learning_rate=0.01, weight_decay=0.01, chunk_elems=1000
        )
        init_g, step_g = build_grouped_offload_step(
            lambda *args: loss_fn_ngrouped(
                args[:-1], args[-1], cfg
            ),
            init_fns=[lambda p=p: p for p in parts],
            optimizers=[HostOffloadAdamW(**kw) for _ in range(n)],
        )
        sg = init_g(None)
        assert len(sg) == n
        for step in range(_NGROUP_STEPS):
            sg, mg = step_g(sg, batch)
            # per-step check: the FIRST grouped step must already
            # match (no warm-up slack hiding a step-1 bug)
            np.testing.assert_allclose(
                float(mg["loss"]), ref_losses[step], rtol=1e-5
            )
        ref = ref_masters[-1]
        bounds = [0, *boundaries, cfg.n_layers]
        for i, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
            np.testing.assert_allclose(
                np.asarray(sg[i].master["layers"]["wq"]),
                ref["layers"]["wq"][lo:hi],
                rtol=2e-4, atol=2e-5,
            )
        np.testing.assert_allclose(
            np.asarray(sg[0].master["embed"]),
            ref["embed"], rtol=2e-4, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(sg[-1].master["lm_head"]),
            ref["lm_head"], rtol=2e-4, atol=2e-5,
        )

    def test_frozen_first_step_when_grads_are_zero(self):
        """A zero-gradient first batch must leave EVERY group's
        master EXACTLY at init (wd=0) — grouped staging must not
        smear updates across group boundaries or inject decay where
        no gradient flowed.  A real second batch must then move
        every group."""
        from dlrover_tpu.optimizers.host_offload import (
            build_grouped_offload_step,
        )

        def loss_grouped(p0, p1, p2, batch):
            pred = (
                p0["w"].astype(jnp.float32)
                + p1["w"].astype(jnp.float32)
                + p2["w"].astype(jnp.float32)
            ) * batch["x"]
            return jnp.mean(pred**2)

        parts = [
            {"w": np.full((300,), 0.5 + i, np.float32)}
            for i in range(3)
        ]
        init_g, step_g = build_grouped_offload_step(
            loss_grouped,
            init_fns=[lambda p=p: p for p in parts],
            optimizers=[
                HostOffloadAdamW(learning_rate=0.05, chunk_elems=128)
                for _ in range(3)
            ],
        )
        sg = init_g(None)
        before = [np.copy(s.master["w"]) for s in sg]
        frozen = {"x": jnp.zeros((300,), jnp.float32)}
        sg, _m = step_g(sg, frozen)
        assert all(s.step == 1 for s in sg)
        for s, b in zip(sg, before):
            np.testing.assert_array_equal(
                np.asarray(s.master["w"]), b
            )
        sg, _m = step_g(sg, {"x": jnp.ones((300,), jnp.float32)})
        assert all(
            not np.allclose(np.asarray(s.master["w"]), b)
            for s, b in zip(sg, before)
        )

    def test_n_group_validation(self):
        from dlrover_tpu.optimizers.host_offload import (
            build_grouped_offload_step,
        )

        with pytest.raises(ValueError, match="at least one"):
            build_grouped_offload_step(lambda b: 0.0, init_fns=[])
        with pytest.raises(ValueError, match="optimizers"):
            build_grouped_offload_step(
                lambda a, b: 0.0,
                init_fns=[lambda: {}, lambda: {}],
                optimizers=[HostOffloadAdamW()],
            )
        # an explicitly-passed empty list is a caller bug, not a
        # request for defaults
        with pytest.raises(ValueError, match="optimizers"):
            build_grouped_offload_step(
                lambda a, b: 0.0,
                init_fns=[lambda: {}, lambda: {}],
                optimizers=[],
            )


def _pinned_host_supported():
    import jax as _jax
    from jax.sharding import SingleDeviceSharding

    try:
        dev = SingleDeviceSharding(_jax.devices()[0])
        host = dev.with_memory_kind("pinned_host")
        x = _jax.device_put(jnp.ones((8,)), host)
        fn = _jax.jit(
            lambda a: _jax.device_put(
                _jax.device_put(a, dev) * 2.0, host
            ),
            in_shardings=(host,),
            out_shardings=host,
        )
        return float(np.asarray(fn(x))[0]) == 2.0
    except Exception:  # noqa: BLE001
        return False


@pytest.mark.skipif(
    not _pinned_host_supported(),
    reason="backend has no pinned_host memory space",
)
class TestPinnedHostBackend:
    """The XLA-memories backend: state chunks live in the TPU host's
    RAM as pinned_host jax arrays; transfers are compiled DMA, never
    the Python client's bandwidth."""

    def test_matches_numpy_backend(self):
        params = _tree_params(jax.random.PRNGKey(3))
        kw = dict(learning_rate=1e-2, weight_decay=0.01,
                  chunk_elems=128)
        opt_np = HostOffloadAdamW(backend="numpy", **kw)
        opt_ph = HostOffloadAdamW(backend="pinned_host", **kw)
        s_np = opt_np.init(params)
        s_ph = opt_ph.init(params)
        for i in range(3):
            grads = jax.tree_util.tree_map(
                lambda p: jnp.asarray(0.1 * p + 0.01 * (i + 1)),
                params,
            )
            s_np = opt_np.apply_gradients(s_np, grads)
            s_ph = opt_ph.apply_gradients(s_ph, grads)
        # identical math, different residency: compare the bf16
        # device params AND the reassembled fp32 masters
        for a, b in zip(
            jax.tree_util.tree_leaves(s_np.params),
            jax.tree_util.tree_leaves(s_ph.params),
        ):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            )
        flat_np = np.concatenate(
            [
                np.asarray(x).reshape(-1)
                for x in jax.tree_util.tree_leaves(s_np.master)
            ]
        )
        flat_ph = np.concatenate(
            [
                np.asarray(c).reshape(-1)
                for leaf in jax.tree_util.tree_leaves(
                    s_ph.master,
                    is_leaf=lambda x: isinstance(x, list),
                )
                for c in leaf
            ]
        )
        np.testing.assert_allclose(flat_np, flat_ph, rtol=1e-6)

    def test_state_resides_in_host_memory(self):
        opt = HostOffloadAdamW(backend="pinned_host", chunk_elems=64)
        state = opt.init({"w": jnp.ones((200,), jnp.float32)})
        for chunk in state.master["w"]:
            assert chunk.sharding.memory_kind == "pinned_host"
        state = opt.apply_gradients(
            state, {"w": jnp.ones((200,), jnp.float32)}
        )
        for chunk in state.mu["w"]:
            assert chunk.sharding.memory_kind == "pinned_host"
        assert state.params["w"].dtype == jnp.bfloat16


class TestInt8Moments:
    """moments="int8": offloaded moments stored blockwise-quantized —
    halves the per-step PCIe stream of the offload path (which the
    op-time report showed is ~59% chunk DMA)."""

    def test_converges_like_fp32(self):
        target = jnp.full((2100,), 2.0)  # not a QBLOCK multiple

        def loss_fn(params, batch):
            pred = params["w"].astype(jnp.float32) * batch["x"]
            return jnp.mean((pred - target) ** 2)

        def run(moments):
            init_state, train_step = build_offloaded_train_step(
                loss_fn,
                lambda rng: {
                    "w": jax.random.normal(rng, (2100,), jnp.float32)
                },
                HostOffloadAdamW(
                    learning_rate=0.1, chunk_elems=1000,
                    backend="numpy", moments=moments,
                ),
            )
            state = init_state(jax.random.PRNGKey(0))
            batch = {"x": jnp.ones((2100,))}
            for _ in range(50):
                state, metrics = train_step(state, batch)
            return float(metrics["loss"]), state

        loss_fp32, _ = run("fp32")
        loss_int8, state = run("int8")
        # int8 moments track the fp32 trajectory to quantization noise
        assert loss_int8 < 0.1
        assert abs(loss_int8 - loss_fp32) < 0.05
        assert state.step == 50

    def test_state_layout_and_memory(self):
        opt = HostOffloadAdamW(
            backend="numpy", moments="int8", chunk_elems=2048
        )
        state = opt.init({"w": np.ones((5000,), np.float32)})
        chunks = state.mu["w"]
        assert len(chunks) == 3  # 2048 + 2048 + 904(padded 1024)
        q, s = chunks[0]
        assert q.dtype == np.int8 and q.shape == (2048,)
        assert s.shape == (2,)
        q_tail, s_tail = chunks[2]
        assert q_tail.shape == (1024,)  # padded to QBLOCK
        # in-place buffer reuse after a step
        state2 = opt.apply_gradients(
            state, {"w": jnp.ones((5000,), jnp.float32)}
        )
        assert state2.mu["w"][0][0] is q
        assert not np.all(q == 0)  # updated in place

    def test_bad_moments_value_raises(self):
        with pytest.raises(ValueError, match="moments"):
            HostOffloadAdamW(moments="fp8")


def _ls_problem(n=320):
    """Least-squares toy problem shared by the fused-path tests."""
    target = jnp.linspace(-2.0, 2.0, n)

    def loss_fn(params, batch):
        pred = params["w"].astype(jnp.float32) * batch["x"]
        return jnp.mean((pred - target) ** 2)

    def init_fn(rng):
        return {"w": jax.random.normal(rng, (n,), jnp.float32)}

    return loss_fn, init_fn, {"x": jnp.ones((n,))}


def _cat_chunks(leaf):
    """Reassemble a fused-state chunk list into one flat array."""
    return np.concatenate([np.asarray(c).reshape(-1) for c in leaf])


class TestFusedOffload:
    """The one-program overlapped update
    (``build_fused_offload_step``): update math fused into the
    train-step jit with host-memory shardings, synchronous or
    one-step-delayed scheduling.  On the CPU mesh the host sharding
    degrades to device memory — the MATH is what these tests pin."""

    def test_sync_matches_chunked_exactly(self):
        """fused sync and the chunked numpy stream are the same
        AdamW: identical masters after several steps on the same
        problem (the update math is shared code; this pins the
        plumbing — sharding, per-leaf H2D/D2H, bias correction)."""
        loss_fn, init_fn, batch = _ls_problem()
        kw = dict(learning_rate=0.05, weight_decay=0.01)

        init_f, step_f = build_fused_offload_step(
            loss_fn, init_fn, HostOffloadAdamW(**kw), delayed=False
        )
        init_c, step_c = build_offloaded_train_step(
            loss_fn, init_fn,
            HostOffloadAdamW(backend="numpy", chunk_elems=100, **kw),
            mode="chunked",
        )
        sf = init_f(jax.random.PRNGKey(7))
        sc = init_c(jax.random.PRNGKey(7))
        assert sf.grads is None
        for _ in range(4):
            sf, mf = step_f(sf, batch)
            sc, mc = step_c(sc, batch)
        np.testing.assert_allclose(
            _cat_chunks(sf.master["w"]),
            sc.master["w"].reshape(-1),
            rtol=1e-5, atol=1e-5,  # fusion-context rounding only
        )
        np.testing.assert_allclose(
            float(mf["loss"]), float(mc["loss"]), rtol=1e-5
        )
        assert int(sf.step) == 4

    def test_delayed_equivalence_to_shifted_grads(self):
        """Delayed mode's DOCUMENTED semantics: step 1 is a true
        no-op (no previous gradients — weight decay gated, bias
        correction counting real moment updates), and step t>=2
        applies the grads computed at step t-1.  T delayed steps must
        therefore land EXACTLY where T-1 synchronous chunked steps on
        the recorded grad sequence land — weight decay included."""
        loss_fn, init_fn, batch = _ls_problem()
        opt = HostOffloadAdamW(learning_rate=0.05, weight_decay=0.01)
        init_f, step_f = build_fused_offload_step(
            loss_fn, init_fn, opt, delayed=True
        )
        state = init_f(jax.random.PRNGKey(3))
        init_master = _cat_chunks(state.master["w"]).copy()
        grads_seen = []
        T = 4
        for _ in range(T):
            state, _m = step_f(state, batch)
            grads_seen.append(
                {"w": np.asarray(state.grads["w"], np.float32)}
            )
            if len(grads_seen) == 1:
                # the step-1 gate: with wd > 0 and no real gradient
                # yet, NOTHING may move before the first real update
                np.testing.assert_array_equal(
                    _cat_chunks(state.master["w"]), init_master
                )
        final_master = _cat_chunks(state.master["w"])

        ref_opt = HostOffloadAdamW(
            learning_rate=0.05, weight_decay=0.01, backend="numpy"
        )
        ref = ref_opt.init(init_fn(jax.random.PRNGKey(3)))
        for g in grads_seen[:-1]:  # shifted schedule: T-1 sync steps
            ref = ref_opt.apply_gradients(
                ref, jax.tree_util.tree_map(jnp.asarray, g)
            )
        np.testing.assert_allclose(
            final_master, ref.master["w"].reshape(-1),
            rtol=1e-5, atol=1e-5,
        )

    def test_delayed_converges_with_bounded_drift(self):
        """One-step staleness must not break optimization: delayed
        reaches the same neighborhood as sync on the toy problem."""
        loss_fn, init_fn, batch = _ls_problem()

        def run(delayed):
            init_f, step_f = build_fused_offload_step(
                loss_fn, init_fn,
                HostOffloadAdamW(learning_rate=0.1),
                delayed=delayed,
            )
            state = init_f(jax.random.PRNGKey(0))
            for _ in range(60):
                state, m = step_f(state, batch)
            return float(m["loss"])

        loss_sync = run(False)
        loss_delayed = run(True)
        assert loss_delayed < 0.05
        assert abs(loss_delayed - loss_sync) < 0.02

    def test_int8_fused_converges(self):
        loss_fn, init_fn, batch = _ls_problem(n=2100)
        init_f, step_f = build_fused_offload_step(
            loss_fn, init_fn,
            HostOffloadAdamW(learning_rate=0.1, moments="int8"),
            delayed=True,
        )
        state = init_f(jax.random.PRNGKey(0))
        q, s = state.mu["w"][0]
        assert q.dtype == jnp.int8 and q.shape[0] % 1024 == 0
        for _ in range(60):
            state, m = step_f(state, batch)
        assert float(m["loss"]) < 0.1
        assert int(state.step) == 60

    def test_auto_mode_selects_by_backend(self):
        """build_offloaded_train_step(mode="auto"): numpy backend
        stays on the chunked path (state is OffloadState), explicit
        fused returns FusedOffloadState."""
        loss_fn, init_fn, batch = _ls_problem()
        init_c, _ = build_offloaded_train_step(
            loss_fn, init_fn,
            HostOffloadAdamW(backend="numpy"),
        )
        assert isinstance(init_c(jax.random.PRNGKey(0)), OffloadState)
        init_f, _ = build_offloaded_train_step(
            loss_fn, init_fn,
            HostOffloadAdamW(backend="numpy"),
            mode="fused_delayed",
        )
        assert isinstance(
            init_f(jax.random.PRNGKey(0)), FusedOffloadState
        )
        with pytest.raises(ValueError, match="mode"):
            build_offloaded_train_step(
                loss_fn, init_fn,
                HostOffloadAdamW(backend="numpy"),
                mode="bogus",
            )

    def test_micro_accumulation_matches_mean_grads(self):
        """micro_steps=K: the program accumulates K microbatch
        gradients (bf16 mean) and streams ONE update — the offload
        throughput lever (amortizes the per-step PCIe stream over K
        microbatches).  The applied update must equal replaying the
        recorded mean grad through the chunked optimizer."""
        loss_fn, init_fn, _ = _ls_problem(n=320)
        batch = {"x": jnp.ones((4 * 320,)).reshape(4 * 320)}

        def loss_b(params, b):
            # per-microbatch view: x is [320] after the split
            return loss_fn(params, {"x": b["x"]})

        opt = HostOffloadAdamW(learning_rate=0.05)
        init_f, step_f = build_fused_offload_step(
            loss_b, init_fn, opt, delayed=True, micro_steps=4
        )
        state = init_f(jax.random.PRNGKey(3))
        grads_seen = []
        for _ in range(3):
            state, m = step_f(state, batch)
            grads_seen.append(
                {"w": np.asarray(state.grads["w"], np.float32)}
            )
        final = _cat_chunks(state.master["w"])

        ref_opt = HostOffloadAdamW(
            learning_rate=0.05, backend="numpy"
        )
        ref = ref_opt.init(init_fn(jax.random.PRNGKey(3)))
        # shifted schedule: the delayed no-op step 1 means T delayed
        # steps == T-1 sync steps on the recorded mean grads
        for g in grads_seen[:-1]:
            ref = ref_opt.apply_gradients(
                ref, jax.tree_util.tree_map(jnp.asarray, g)
            )
        np.testing.assert_allclose(
            final, ref.master["w"].reshape(-1), rtol=1e-5, atol=1e-5
        )

    def test_chunked_micro_matches_fused_micro(self):
        """The chunked multi-dispatch accumulation (one program per
        microbatch + donated adds — what the 1.8B proofs run) is the
        same math as the fused in-program accumulation."""
        loss_fn, init_fn, _ = _ls_problem(n=320)
        batch = {"x": jnp.ones((4 * 320,))}

        def loss_b(params, b):
            return loss_fn(params, {"x": b["x"]})

        init_c, step_c = build_offloaded_train_step(
            loss_b, init_fn,
            HostOffloadAdamW(
                learning_rate=0.05, backend="numpy", chunk_elems=100
            ),
            mode="chunked", micro_steps=4,
        )
        init_f, step_f = build_fused_offload_step(
            loss_b, init_fn,
            HostOffloadAdamW(learning_rate=0.05),
            delayed=False, micro_steps=4,
        )
        sc = init_c(jax.random.PRNGKey(5))
        sf = init_f(jax.random.PRNGKey(5))
        for _ in range(3):
            sc, mc = step_c(sc, batch)
            sf, mf = step_f(sf, batch)
        # bf16 accumulation rounds differently across program
        # boundaries (separate adds) vs one fused program — the
        # trajectories agree to bf16 grad noise, not bitwise
        np.testing.assert_allclose(
            sc.master["w"].reshape(-1), _cat_chunks(sf.master["w"]),
            rtol=2e-3, atol=2e-4,
        )
        np.testing.assert_allclose(
            float(mc["loss"]), float(mf["loss"]), rtol=1e-4
        )

    def test_micro_accumulation_converges(self):
        loss_fn, init_fn, _ = _ls_problem(n=256)
        batch = {"x": jnp.ones((2 * 256,))}

        def loss_b(params, b):
            return loss_fn(params, {"x": b["x"]})

        init_f, step_f = build_fused_offload_step(
            loss_b, init_fn,
            HostOffloadAdamW(learning_rate=0.1),
            delayed=True, micro_steps=2,
        )
        state = init_f(jax.random.PRNGKey(0))
        for _ in range(60):
            state, m = step_f(state, batch)
        assert float(m["loss"]) < 0.05

    def test_chunked_prefetch_window_matches_no_prefetch(self):
        """start_prefetch feeds the first window; results must be
        identical to the unprefetched stream."""
        params = _tree_params(jax.random.PRNGKey(3))
        kw = dict(
            learning_rate=1e-2, weight_decay=0.01, chunk_elems=128
        )
        opt = HostOffloadAdamW(backend="numpy", **kw)
        s_a = opt.init(params)
        s_b = opt.init(params)
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(0.1 * p), params
        )
        pre = opt.start_prefetch(s_a)
        assert pre and len(pre) <= opt.window
        s_a = opt.apply_gradients(s_a, grads, prefetched=pre)
        s_b = opt.apply_gradients(s_b, grads)
        np.testing.assert_array_equal(s_a.master["w"], s_b.master["w"])
        np.testing.assert_array_equal(s_a.master["m"], s_b.master["m"])


class TestRollingPrefetch:
    """The double-buffered DMA window (``_RollingPrefetch``): every
    chunk's H2D — not only the first window's — is dispatched ahead
    of its compute, with ``DLROVER_TPU_OFFLOAD_BUFFERED=0`` restoring
    the legacy one-shot prefetch exactly."""

    def _opt_and_state(self, chunk=128):
        params = _tree_params(jax.random.PRNGKey(4))
        opt = HostOffloadAdamW(
            backend="numpy", learning_rate=1e-2,
            weight_decay=0.01, chunk_elems=chunk,
        )
        return opt, opt.init(params), params

    def test_rolling_is_default_and_bounded(self):
        from dlrover_tpu.optimizers.host_offload import (
            _RollingPrefetch,
        )

        opt, state, _ = self._opt_and_state()
        pre = opt.start_prefetch(state)
        assert isinstance(pre, _RollingPrefetch)
        # initial fill is exactly the window
        assert len(pre) == opt.window
        # consuming refills: the window stays bounded, never drains
        # to zero until the stream end
        first = pre.get((0, 0))
        assert first is not None and len(pre) == opt.window
        # a missed key still refills (keeps the stream rolling)
        assert pre.get((99, 99)) is None

    def test_rolling_matches_one_shot_and_no_prefetch(
        self, monkeypatch
    ):
        opt, s_roll, params = self._opt_and_state()
        _, s_one, _ = self._opt_and_state()
        _, s_none, _ = self._opt_and_state()
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(0.1 * p), params
        )
        for _ in range(3):
            pre = opt.start_prefetch(s_roll)
            s_roll = opt.apply_gradients(
                s_roll, grads, prefetched=pre
            )
            monkeypatch.setenv("DLROVER_TPU_OFFLOAD_BUFFERED", "0")
            pre1 = opt.start_prefetch(s_one)
            # the kill-switch restores the legacy one-shot dict
            assert isinstance(pre1, dict)
            assert len(pre1) <= opt.window
            s_one = opt.apply_gradients(
                s_one, grads, prefetched=pre1
            )
            monkeypatch.delenv("DLROVER_TPU_OFFLOAD_BUFFERED")
            s_none = opt.apply_gradients(s_none, grads)
        for key in ("w", "b", "m"):
            np.testing.assert_array_equal(
                s_roll.master[key], s_one.master[key]
            )
            np.testing.assert_array_equal(
                s_roll.master[key], s_none.master[key]
            )

    def test_offload_copy_span_emitted(self, tmp_path):
        from dlrover_tpu.observability import events as ev

        path = tmp_path / "timeline.jsonl"
        ev.set_default_event_logger(
            ev.EventLogger(path=str(path))
        )
        try:
            opt, state, params = self._opt_and_state()
            grads = jax.tree_util.tree_map(
                lambda p: jnp.asarray(0.1 * p), params
            )
            pre = opt.start_prefetch(state)
            opt.apply_gradients(state, grads, prefetched=pre)
        finally:
            ev.set_default_event_logger(None)
        spans = [
            e for e in ev.read_events(str(path))
            if e["name"] == "offload_copy"
        ]
        assert spans, "no offload_copy span emitted"
        labels = spans[-1]["labels"]
        assert labels["bytes"] > 0
        assert labels["throughput_gbps"] > 0
        assert labels["buffered"] is True


class TestTransferQuant:
    """Quantized optimizer-state TRANSFERS: fp32 moments stay fp32 in
    host storage but cross the host boundary as int8+scales
    (``DLROVER_TPU_OFFLOAD_QUANT``) — ~4x less moment traffic on the
    link the offload proof is bound by."""

    STEPS = 40
    LR = 0.1

    def _run(self, steps=STEPS, n=2100):
        target = jnp.full((n,), 2.0)

        def loss_fn(params, batch):
            pred = params["w"].astype(jnp.float32) * batch["x"]
            return jnp.mean((pred - target) ** 2)

        init_state, train_step = build_offloaded_train_step(
            loss_fn,
            lambda rng: {
                "w": jax.random.normal(rng, (n,), jnp.float32)
            },
            HostOffloadAdamW(
                learning_rate=self.LR, chunk_elems=1000,
                backend="numpy",
            ),
        )
        state = init_state(jax.random.PRNGKey(0))
        batch = {"x": jnp.ones((n,))}
        for _ in range(steps):
            state, metrics = train_step(state, batch)
        return float(metrics["loss"]), state

    def test_dequant_equivalence_tolerance(self, monkeypatch):
        """The quantized wire format tracks the fp32 trajectory to
        what its rounding can accumulate: same convergence, masters
        within that bound, host storage still fp32 numpy updated in
        place.

        The bound.  Each step the moments cross as int8 with a step of
        ``max|block| / 127`` (``nu`` as ``sqrt(nu)``), so an element at
        its block's scale takes a rounding error of at most
        ``eps = 1/254`` of its value in ``mu`` and in ``sqrt(nu)``.
        The errors are REMEMBERED: ``mu`` averages over ``1/(1-b1)`` =
        10 steps, ``nu`` (b2 = 0.999) over more steps than the test
        runs, so after ``t`` steps the ratio ``mu/sqrt(nu)`` that Adam
        steps by is off by at most ``eps * (t + 10)``, and the master,
        which moves ``lr *`` ratio a step, by at most
        ``lr * eps * (T^2/2 + 10 T)`` after ``T`` = 40 steps: 0.47.
        Rounding to nearest is unbiased, so the TYPICAL element walks
        randomly instead — ``sqrt(t)`` and ``sqrt(10)`` in place of
        ``t`` and 10 — and its error a step is not ``eps`` but the RMS
        of a rounding uniform over ``+-eps``, ``eps / sqrt(3)``:
        ``lr * eps / sqrt(3) * (2/3 T^1.5 + sqrt(10) T)`` = 0.067,
        and the mean is held to that.  Both figures are estimates
        from the wire's step, not proofs; what they are worth was
        read once against the run: the masters' RMS difference is
        0.066 and their mean 0.049, and a wire with HALF the levels
        (a step of ``max|block| / 63.5``) reads mean 0.179 and max
        0.350, so a step twice as coarse fails the mean by 2.7x.
        The elements that come nearest the worst case are the ones
        that started farthest from the target (they are their block's
        scale, and still travelling at step 40: 39 of 2100 lay outside
        the ``rtol 0.1`` this test used to ask for, by up to 0.198);
        an element far below its block's scale is one already at the
        target, where the ratio is sign noise in both runs and the
        masters are a step or two of ``lr`` apart, inside the same
        bound."""
        monkeypatch.delenv("DLROVER_TPU_OFFLOAD_QUANT", raising=False)
        loss_fp32, s_fp32 = self._run()
        monkeypatch.setenv("DLROVER_TPU_OFFLOAD_QUANT", "1")
        loss_q, s_q = self._run()
        assert loss_q < 0.1
        assert abs(loss_q - loss_fp32) < 0.05
        assert s_q.mu["w"].dtype == np.float32  # storage unchanged
        t, eps, memory = float(self.STEPS), 1.0 / 254.0, 10.0
        worst = self.LR * eps * (t * t / 2 + memory * t)
        typical = self.LR * eps / 3.0 ** 0.5 * (
            2.0 / 3.0 * t ** 1.5 + memory ** 0.5 * t
        )
        diff = np.abs(
            np.asarray(s_q.master["w"]) - np.asarray(s_fp32.master["w"])
        )
        assert diff.max() <= worst, (diff.max(), worst)
        assert diff.mean() <= typical, (diff.mean(), typical)
        # and the wire really was lossy: a run that never quantized
        # would pass the bounds with zeros
        assert diff.max() > 0.0

    def test_kill_switch_restores_exact_fp32_wire(self, monkeypatch):
        """QUANT=0 must be byte-identical to the unset default on a
        CPU backend (where quantized transfers default off)."""
        monkeypatch.delenv("DLROVER_TPU_OFFLOAD_QUANT", raising=False)
        _, s_default = self._run(steps=5)
        monkeypatch.setenv("DLROVER_TPU_OFFLOAD_QUANT", "0")
        _, s_off = self._run(steps=5)
        np.testing.assert_array_equal(
            s_default.master["w"], s_off.master["w"]
        )
        np.testing.assert_array_equal(
            s_default.mu["w"], s_off.mu["w"]
        )

    def test_quant_wire_format_round_trip(self):
        """Host-side quant/deq mirrors the in-program kernels' block
        layout: a round-trip reconstructs within int8 step size."""
        from dlrover_tpu.optimizers.host_offload import (
            _np_deq_chunk,
            _np_quant_chunk,
        )

        x = np.random.RandomState(0).randn(2100).astype(np.float32)
        q, s = _np_quant_chunk(x)
        assert q.dtype == np.int8 and q.shape[0] % 1024 == 0
        back = _np_deq_chunk(q, s, 2100)
        np.testing.assert_allclose(
            back, x, atol=float(np.max(np.abs(x))) / 127 + 1e-6
        )

    def test_prefetched_quant_matches_unprefetched(self, monkeypatch):
        """The rolling window and the quantized wire compose: same
        result with and without prefetch."""
        monkeypatch.setenv("DLROVER_TPU_OFFLOAD_QUANT", "1")
        params = _tree_params(jax.random.PRNGKey(5))
        opt = HostOffloadAdamW(
            backend="numpy", learning_rate=1e-2, chunk_elems=128
        )
        s_a = opt.init(params)
        s_b = opt.init(params)
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(0.1 * p), params
        )
        pre = opt.start_prefetch(s_a)
        s_a = opt.apply_gradients(s_a, grads, prefetched=pre)
        s_b = opt.apply_gradients(s_b, grads)
        np.testing.assert_array_equal(
            s_a.master["w"], s_b.master["w"]
        )
        np.testing.assert_array_equal(s_a.mu["w"], s_b.mu["w"])

    @pytest.mark.parametrize("buffered", ["1", "0"])
    def test_env_flip_between_prefetch_and_apply(
        self, monkeypatch, buffered
    ):
        """The staged window pins its quant arity: flipping the
        kill-switch between start_prefetch and apply_gradients must
        consume the in-flight chunks as staged, not crash (or worse,
        misread int8 tuples as fp32)."""
        monkeypatch.setenv("DLROVER_TPU_OFFLOAD_BUFFERED", buffered)
        monkeypatch.setenv("DLROVER_TPU_OFFLOAD_QUANT", "1")
        params = _tree_params(jax.random.PRNGKey(6))
        opt = HostOffloadAdamW(
            backend="numpy", learning_rate=1e-2, chunk_elems=128
        )
        s_a = opt.init(params)
        s_b = opt.init(params)
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(0.1 * p), params
        )
        pre = opt.start_prefetch(s_a)
        monkeypatch.setenv("DLROVER_TPU_OFFLOAD_QUANT", "0")
        s_a = opt.apply_gradients(s_a, grads, prefetched=pre)
        # reference: the whole step staged AND applied quantized
        monkeypatch.setenv("DLROVER_TPU_OFFLOAD_QUANT", "1")
        s_b = opt.apply_gradients(s_b, grads)
        np.testing.assert_array_equal(
            s_a.master["w"], s_b.master["w"]
        )
