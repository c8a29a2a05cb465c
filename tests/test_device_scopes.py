"""The device scopes of the two hot programs (ISSUE 38), from the LOWERED
text: counts only, no device, no times.

``jax.named_scope`` puts a name on the path (``op_name``) of every
instruction traced inside it; the device trace keeps that path with the
operation, and ``benchmarks/readers_scopes.py`` splits the device's time
by it.  Here each program is lowered on the CPU at a tiny size and the
paths of its StableHLO operations are read from the text's locations
(``#locN = loc("jit(f)/jvp(attn)/dot_general"(...))``) and put through
the SAME rule the reader uses (``readers_scopes.classify``):

- every ``dot_general``, ``convolution`` and ``custom_call``, and every
  instruction of a scan's body but the scan's own plumbing, carries
  exactly one part of the closed set (a serving step program: exactly
  one role too);
- the backward pass and the ``jax.checkpoint`` replay of a part are told
  apart by the path, not by scopes of their own;
- a scope outside ``DEVICE_SCOPES`` fails the lint;
- the optimized programs count the instructions they counted before the
  scopes went in: a scope is metadata.
"""

import os
import re
import subprocess
import sys
from functools import cache, partial

import jax
import jax.numpy as jnp
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import readers_scopes  # noqa: E402

from dlrover_tpu.models import falcon_h1, llama  # noqa: E402
from dlrover_tpu.observability.events import (  # noqa: E402
    DEVICE_SCOPE_PARTS,
    DEVICE_SCOPE_ROLES,
    DEVICE_SCOPES,
)

_LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')
_OP = re.compile(r'"?(stablehlo\.[a-z_]+)"?[ (].*loc\((#loc\d+)\)\s*$')
_FUNC = re.compile(r'^\s*func\.func (?:public |private )?@([\w.\-]+)\(')
_CALL = re.compile(r'\bcall @([\w.\-]+)\(.*loc\((#loc\d+)\)\s*$')
#: what a scan itself adds to its body: slicing the stacked inputs,
#: stacking the outputs, the counter — no part of the model
_PLUMBING = {
    "add", "sub", "lt", "select_n", "squeeze", "broadcast_in_dim",
    "dynamic_slice", "dynamic_update_slice", "convert_element_type",
    "add_any", "reshape", "mul", "while", "cond", "body", "closed_call",
    "remat2", "checkpoint", "pjit", "jit",
}
HEAVY = {"stablehlo.dot_general", "stablehlo.convolution",
         "stablehlo.custom_call"}


def operations(lowered):
    """``[(stablehlo op, path)]`` of a lowered program.  An operation in
    a function of its own (a scan's checkpointed body, a nested ``jit``)
    carries the path from that function on; the call carries the rest,
    and XLA joins the two when it inlines the call — as is done here,
    once for every site a function is called from."""
    text = lowered.as_text(debug_info=True).splitlines()
    paths = dict(m.groups() for m in map(_LOC.match, text) if m)
    ops, calls, func = [], [], None
    for line in text:
        m = _FUNC.match(line)
        if m:
            func = m.group(1)
            continue
        m = _CALL.search(line)
        if m and m.group(2) in paths:
            calls.append((func, m.group(1), paths[m.group(2)]))
            continue
        m = _OP.search(line)
        # (a constant is hoisted to where its function starts and keeps
        # only the primitive's name: not an instruction of any scope)
        if m and m.group(2) in paths and m.group(1) != "stablehlo.constant":
            ops.append((func, m.group(1), paths[m.group(2)]))

    def join(prefix, path):
        return f"{prefix}/{path}" if prefix else path

    prefixes, grew = {"main": {""}}, True
    while grew:
        grew = False
        for caller, callee, path in calls:
            new = {join(p, path) for p in prefixes.get(caller, ())}
            if not new <= prefixes.setdefault(callee, set()):
                prefixes[callee] |= new
                grew = True
    return [
        (op, join(prefix, path))
        for func, op, path in ops for prefix in prefixes.get(func, ())
    ]


def scopes_on(path):
    """Every scope of the closed set on a path, outermost first."""
    words = readers_scopes._WORD.findall(
        readers_scopes._JIT.sub("", path)
    )
    return [w for w in words if w in DEVICE_SCOPES]


def primitive(path):
    return path.rstrip(":").rsplit("/", 1)[-1]


def test_the_reader_repeats_the_programs_closed_set():
    assert set(readers_scopes.ROLES) == DEVICE_SCOPE_ROLES
    # ``indexer`` (PR 42) is entered INSIDE ``attn``: the reader's set,
    # which a program PR may not edit, counts its time under ``attn``,
    # and ``readers_sparse.scope_share`` reads it with the part added
    # ``window`` / ``full`` (PR 44) likewise: the kind of a layer's
    # attention, inside ``attn``; ``linear`` (PR 49) too, and
    # ``gdn_scan`` inside ``linear``: a gated delta-rule layer and its
    # prefill's chunk scan; ``latent`` (PR 53) likewise: latent
    # attention's own work beside its indexer; ``kda_scan`` (PR 57)
    # inside ``linear`` as ``gdn_scan`` is: Kimi Delta Attention's
    # chunk scan; ``conv`` (PR 59) inside ``attn`` as the kinds are: a
    # gated short-convolution layer
    assert set(readers_scopes.PARTS) | {
        "indexer", "window", "full", "linear", "gdn_scan", "kda_scan",
        "latent", "conv",
    } == DEVICE_SCOPE_PARTS
    assert not DEVICE_SCOPE_ROLES & DEVICE_SCOPE_PARTS


def test_the_operators_table_has_a_row_for_every_scope():
    """``docs/observability.md``, "The two hot loops on the profiler's
    clock": name, emitted by, covers — and the path rule beside it."""
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        rows = [
            line.split("|")[1] for line in f
            if line.startswith("|") and "| device scope" in line
        ]
    named = set(re.findall(r"`(\w+)`", " ".join(rows)))
    assert named == DEVICE_SCOPES


# ------------------------------------------------------------ train step

TRAIN_VARIANTS = {
    "remat_full-fused_ce": ("full", True),
    "remat_full-plain_ce": ("full", False),
    "remat_none-fused_ce": ("none", True),
    "remat_none-plain_ce": ("none", False),
    # a rung of the ladder (parallel/remat.py) keeps named values and
    # replays the rest of the block: what it replays still carries
    # ``rematted_computation`` and its part
    "remat_flash-fused_ce": ("flash", True),
    "remat_qkv-fused_ce": ("qkv", True),
    "remat_matmuls-fused_ce": ("matmuls", True),
}
TRAIN_PARTS = ("embed", "attn", "mlp", "head_loss", "optimizer")


@pytest.fixture(scope="module")
def train_ops():
    """``variant -> operations`` of the tiny train step as
    ``auto_accelerate`` jits it (one device)."""
    from dlrover_tpu.accelerate import auto_accelerate, load_strategy
    from dlrover_tpu.parallel.mesh import destroy_parallel_mesh

    out = {}
    for variant, (remat, fused) in TRAIN_VARIANTS.items():
        cfg = llama.LlamaConfig.tiny(remat=remat)
        try:
            fns = auto_accelerate(
                loss_fn=lambda p, b: llama.loss_fn(p, b, cfg, fused_ce=fused),
                optimizer=optax.adamw(1e-3),
                init_params_fn=lambda rng: llama.init_params(rng, cfg),
                param_axes=llama.param_logical_axes(cfg),
                load_strategy=load_strategy({"data": 1}),
                devices=jax.devices()[:1],
            ).fns
            batch = {"tokens": jax.ShapeDtypeStruct((2, 17), jnp.int32)}
            out[variant] = operations(
                fns.train_step.lower(fns.state_shape, batch)
            )
        finally:
            destroy_parallel_mesh()
    return out


@pytest.mark.parametrize("part", TRAIN_PARTS)
@pytest.mark.parametrize("variant", sorted(TRAIN_VARIANTS))
def test_train_step_part_and_its_directions(variant, part, train_ops):
    """Each part is on the program, forward and (but for the optimizer)
    backward; the replay is there exactly where a ``jax.checkpoint``
    region is replayed: the scanned block under every remat policy but
    ``none`` (under ``matmuls`` the two norms and ``silu * up`` are
    what is left of it), the chunked cross-entropy's logits when the
    loss is fused."""
    remat, fused = TRAIN_VARIANTS[variant]
    directions = {
        d for _, path in train_ops[variant]
        for role, p, d in [readers_scopes.classify(path)]
        if p == part and role == readers_scopes.NONE
    }
    want = {"fwd"}
    if part != "optimizer":
        want.add("bwd")
    if (part in ("attn", "mlp") and remat != "none") or (
        part == "head_loss" and fused
    ):
        want.add("recompute")
    assert directions == want


@pytest.mark.parametrize("variant", sorted(TRAIN_VARIANTS))
def test_train_step_heavy_instructions_carry_one_part(variant, train_ops):
    ops = train_ops[variant]
    heavy = [(op, path) for op, path in ops if op in HEAVY]
    assert len(heavy) >= 20
    for op, path in heavy:
        found = scopes_on(path)
        assert len(found) == 1 and found[0] in TRAIN_PARTS, (op, path)
    # a scan's body: the model's instructions are scoped, what is not
    # is the scan's own plumbing
    body = [path for _, path in ops if "/while/body/" in path]
    assert len(body) > 100
    loose = {primitive(p) for p in body if not scopes_on(p)}
    assert loose <= _PLUMBING, loose - _PLUMBING


# -------------------------------------------------- serving step programs

LLAMA = llama.LlamaConfig.tiny()
FALCON = falcon_h1.FalconH1Config.tiny()
LANES, BLOCKS, BLOCK, MAX_BLOCKS, CHUNK, WINDOW = 4, 16, 4, 8, 8, 3
DENSE_PARTS = ("embed", "attn", "mlp", "head")


def _spec(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _pool(cfg, kv_heads, head_dim, layers, state=()):
    shape = (layers, BLOCKS, BLOCK, kv_heads, head_dim)
    pool = {"k": _spec(shape, cfg.dtype), "v": _spec(shape, cfg.dtype)}
    for leaf, (tail, dtype) in state:
        pool[leaf] = _spec((layers, LANES) + tail, dtype)
    return pool


def _lower_decode(model_step, params, pool):
    """The decode step as the scheduler jits it: the model's program
    and the sampler behind it."""
    from dlrover_tpu.rl.scheduler import decode_program

    return jax.jit(decode_program(model_step, 1.0, True, MAX_BLOCKS)).lower(
        params, pool, _spec((LANES,)), _spec((LANES, MAX_BLOCKS + 2)),
        _spec((LANES, 2), jnp.uint32),
    )


def _llama_program(name):
    cfg = LLAMA
    params = jax.eval_shape(
        lambda: llama.serving_params(
            llama.init_params(jax.random.PRNGKey(0), cfg), cfg
        )
    )
    pool = _pool(cfg, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers)
    lanes = (
        _spec((LANES, MAX_BLOCKS)), _spec((LANES,)),
        _spec((LANES,), jnp.bool_),
    )
    if name == "decode":
        return _lower_decode(
            partial(llama.paged_decode_step, cfg=cfg), params, pool
        )
    if name == "prefill":
        return jax.jit(partial(llama.paged_prefill_chunk, cfg=cfg)).lower(
            params, _spec((1, CHUNK)), pool, _spec((MAX_BLOCKS,)), _spec(())
        )
    fn = (
        llama.paged_verify_step if name == "verify"
        else llama.paged_verify_write_step
    )
    return jax.jit(partial(fn, cfg=cfg)).lower(
        params, _spec((LANES, WINDOW)), pool, *lanes
    )


def _falcon_program(name):
    cfg = FALCON
    params = jax.eval_shape(
        lambda: falcon_h1.serving_params(
            falcon_h1.init_params(jax.random.PRNGKey(0), cfg), cfg
        )
    )
    pool = _pool(
        cfg, cfg.num_key_value_heads, cfg.head_dim, cfg.num_hidden_layers,
        state=cfg.lane_state().items(),
    )
    if name == "decode":
        return _lower_decode(
            partial(falcon_h1.paged_decode_step, cfg=cfg), params, pool
        )
    return jax.jit(partial(falcon_h1.paged_prefill_chunk, cfg=cfg)).lower(
        params, _spec((1, CHUNK)), pool, _spec((MAX_BLOCKS,)), _spec(()),
        _spec(()), _spec(()),
    )


#: program -> (builder, its one role, its parts)
SERVING = {
    "llama-decode": (
        partial(_llama_program, "decode"), "decode",
        DENSE_PARTS + ("sample",),
    ),
    "llama-prefill": (
        partial(_llama_program, "prefill"), "prefill", DENSE_PARTS
    ),
    "llama-verify": (
        partial(_llama_program, "verify"), "verify", DENSE_PARTS
    ),
    "llama-verify_write": (
        partial(_llama_program, "verify_write"), "verify", DENSE_PARTS
    ),
    "falcon_h1-decode": (
        partial(_falcon_program, "decode"), "decode",
        DENSE_PARTS + ("ssm", "sample"),
    ),
    "falcon_h1-prefill": (
        partial(_falcon_program, "prefill"), "prefill",
        DENSE_PARTS + ("ssm",),
    ),
}


@pytest.fixture(scope="module")
def serving_ops():
    cache = {}

    def get(program):
        if program not in cache:
            cache[program] = operations(SERVING[program][0]())
        return cache[program]

    return get


@pytest.mark.parametrize(
    "program,part",
    [(prog, part) for prog, (_, _, parts) in sorted(SERVING.items())
     for part in parts],
)
def test_serving_program_part_lies_under_its_role(program, part, serving_ops):
    _, role, _ = SERVING[program]
    keys = {
        readers_scopes.classify(path) for _, path in serving_ops(program)
    }
    under = {(r, d) for r, p, d in keys if p == part}
    assert under == {(role, "fwd")}


@pytest.mark.parametrize("program", sorted(SERVING))
def test_serving_program_heavy_instructions_carry_one_role_and_part(
    program, serving_ops
):
    _, role, parts = SERVING[program]
    ops = serving_ops(program)
    heavy = [(op, path) for op, path in ops if op in HEAVY]
    assert len(heavy) >= 8
    for op, path in heavy:
        found = scopes_on(path)
        assert [s for s in found if s in DEVICE_SCOPE_ROLES] == [role], path
        assert len(
            [s for s in found if s in DEVICE_SCOPE_PARTS]
        ) == 1, (op, path)
    body = [path for _, path in ops if "/while/body/" in path]
    loose = {primitive(p) for p in body if len(scopes_on(p)) < 2}
    assert loose <= _PLUMBING, loose - _PLUMBING


def _lfm2_program(name):
    """LFM2-MoE's step programs as the scheduler jits them, over the
    pool its config asks ``rl/kv_cache`` for (conv tails, pages in rows
    of two heads)."""
    from dlrover_tpu.models import lfm2_moe
    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config
    from dlrover_tpu.rl.scheduler import decode_program, prefill_programs

    cfg = lfm2_moe.Lfm2MoeConfig.tiny()
    params = jax.eval_shape(
        lambda: lfm2_moe.serving_params(
            lfm2_moe.init_params(jax.random.PRNGKey(0), cfg), cfg
        )
    )
    pool = jax.eval_shape(lambda: init_block_pool(
        paged_cache_config(cfg, BLOCKS, BLOCK, LANES, CHUNK)
    ))
    if name == "decode":
        step = partial(lfm2_moe.paged_decode_step, cfg=cfg)
        return jax.jit(
            decode_program(step, 1.0, True, MAX_BLOCKS, True)
        ).lower(
            params, pool, _spec((LANES,)), _spec((LANES, MAX_BLOCKS + 2)),
            _spec((LANES, 2), jnp.uint32),
        )
    chunk = partial(lfm2_moe.paged_prefill_chunk, cfg=cfg)
    _, last = prefill_programs(chunk, 1.0, True, True, True)
    return jax.jit(last).lower(
        params, pool, _spec((LANES,)), _spec((LANES, 2), jnp.uint32),
        _spec((1, CHUNK)), _spec((MAX_BLOCKS,)), _spec(()), _spec(()),
        _spec(()),
    )


@pytest.mark.parametrize("role", ["decode", "prefill"])
def test_short_conv_layers_run_under_conv_inside_attn(role):
    """``models/lfm2_moe.py``: every heavy instruction carries its one
    role; under ``attn`` it carries the layer's kind as well — ``conv``
    (the projections around the taps, the tail's read and write) or
    ``full`` — and elsewhere one part; the tail is written under
    ``conv`` and nowhere else."""
    ops = operations(_lfm2_program(role))
    heavy = [(op, path) for op, path in ops if op in HEAVY]
    assert len(heavy) >= 8
    kinds = set()
    for op, path in heavy:
        found = scopes_on(path)
        assert [s for s in found if s in DEVICE_SCOPE_ROLES] == [role], path
        parts = [s for s in found if s in DEVICE_SCOPE_PARTS]
        if parts[:1] == ["attn"]:
            assert len(parts) == 2 and parts[1] in ("conv", "full"), path
            kinds.add(parts[1])
        else:
            assert len(parts) == 1, (op, path)
    assert kinds == {"conv", "full"}
    assert {"mlp", "head", "sample", "embed"} <= {
        s for _, path in ops for s in scopes_on(path)
    }
    # the reader's closed set lacks ``conv``: it counts the time under
    # ``attn``, and ``readers_sparse.scope_share`` reads it with the
    # part added
    assert {readers_scopes.classify(path)[1] for _, path in ops
            if "conv" in scopes_on(path)} == {"attn"}


def _prefill_programs(name):
    """``(cfg, chunk without head, last chunk)`` as the scheduler jits
    them (``rl/scheduler.prefill_programs``, logprobs captured as in
    the cells), lowered."""
    from dlrover_tpu.rl.scheduler import prefill_programs

    if name == "llama":
        cfg, mod, lane_state = LLAMA, llama, False
        pool = _pool(cfg, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers)
    else:
        cfg, mod, lane_state = FALCON, falcon_h1, True
        pool = _pool(
            cfg, cfg.num_key_value_heads, cfg.head_dim,
            cfg.num_hidden_layers, state=cfg.lane_state().items(),
        )
    params = jax.eval_shape(
        lambda: mod.serving_params(
            mod.init_params(jax.random.PRNGKey(0), cfg), cfg
        )
    )
    model = partial(mod.paged_prefill_chunk, cfg=cfg)
    prefill, last = prefill_programs(model, 1.0, True, lane_state)
    chunk = (
        _spec((1, CHUNK)), _spec((MAX_BLOCKS,)), _spec(()), _spec(()),
        _spec(()),
    )
    return (
        cfg,
        jax.jit(prefill).lower(params, pool, *chunk),
        jax.jit(last).lower(
            params, pool, _spec((LANES,)), _spec((LANES, 2), jnp.uint32),
            *chunk,
        ),
    )


@pytest.fixture(scope="module")
def prefill_lowered():
    return cache(_prefill_programs)


def _parts(lowered):
    return {readers_scopes.classify(path)[:2] for _, path in operations(lowered)}


@pytest.mark.parametrize("name", ["llama", "falcon_h1"])
def test_a_chunk_that_is_not_the_last_has_no_head(name, prefill_lowered):
    """Its logits are dropped inside the program, and with them goes
    everything that made them: no operation under ``prefill`` /
    ``head``, no ``lm_head`` among the arguments, nothing of the
    vocabulary's width computed."""
    cfg, prefill, _ = prefill_lowered(name)
    parts = _parts(prefill)
    assert ("prefill", "mlp") in parts and ("prefill", "attn") in parts
    assert not {p for p in parts if p[1] in ("head", "sample")}, parts
    text = prefill.as_text()
    assert cfg.vocab_size == 256  # no other width of the tiny models
    assert "x256xf32>" not in text and "<256xf32>" not in text
    assert not [
        line for line in text.splitlines()
        if "stablehlo.dot_general" in line and "x256x" in line
    ]


@pytest.mark.parametrize("name", ["llama", "falcon_h1"])
def test_the_last_chunks_head_hands_on_one_row(name, prefill_lowered):
    """As written, the last chunk's program holds the model's whole
    head and a masked sum that leaves one row of it, all under
    ``prefill`` / ``head``; the sum is the last that sees the chunk's
    width, so the compiler can fuse it into the product (that it does,
    and writes one row, is pinned on the compiled program:
    ``tests/test_tpu_compile_*.py``)."""
    _, _, last = prefill_lowered(name)
    under_head = {
        primitive(path) for _, path in operations(last)
        if readers_scopes.classify(path)[:2] == ("prefill", "head")
    }
    assert {"dot_general", "select_n", "reduce_sum"} <= under_head, under_head
    wide = [
        line for line in last.as_text().split("func.func")[1].splitlines()
        if f"<1x{CHUNK}x256xf32>" in line
    ]
    assert "stablehlo.dot_general" in wide[0], wide[0]
    assert "stablehlo.reduce" in wide[-1], wide[-1]
    assert wide[-1].endswith("-> tensor<1x256xf32>"), wide[-1]


@pytest.mark.parametrize("name", ["llama", "falcon_h1"])
def test_the_prompts_first_token_is_sampled_under_prefill(
    name, prefill_lowered
):
    """The first token of a prompt, from the one row of its last
    chunk's head, in the last chunk's own program: the sampler and the
    logprob lie under ``prefill`` / ``sample``, the head's row under
    ``prefill`` / ``head``, and no part lies under another role."""
    _, _, last = prefill_lowered(name)
    ops, parts = operations(last), _parts(last)
    assert {("prefill", "head"), ("prefill", "sample")} <= parts
    assert {role for role, _ in parts} <= {"prefill", "-"}
    # what only the sampler's random bits are made of
    drawn = [
        path for _, path in ops
        if primitive(path) in ("xor", "shift_right_logical")
    ]
    assert drawn
    for path in drawn:
        assert readers_scopes.classify(path)[:2] == ("prefill", "sample")


# ------------------------------------------------------------- the lint


def test_a_scope_outside_the_closed_set_fails_the_lint(tmp_path):
    bad = tmp_path / "bad_scope.py"
    bad.write_text(
        "import jax\n"
        "def f(x, name):\n"
        "    with jax.named_scope('attn'):\n"          # fine
        "        x = x + 1\n"
        "    with jax.named_scope('attention'):\n"     # not in the set
        "        x = x * 2\n"
        "    with jax.named_scope(name):\n"            # not a literal
        "        return x\n"
    )
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_event_schema.py"), str(bad)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert "named_scope('attention')" in proc.stdout


# -------------------------------------------- a scope changes no program

#: instructions of the optimized CPU programs (``compiled.as_text()``,
#: lines that define one), counted on the parent of the PR that added
#: the scopes and equal with them: a scope is ``op_name`` metadata
PARENT_INSTRUCTIONS = {"train": 2534, "decode": 1142}


def _instructions(compiled):
    return sum(1 for line in compiled.as_text().splitlines() if " = " in line)


def test_scopes_add_no_instruction_to_the_optimized_programs():
    cfg = llama.LlamaConfig.tiny()
    opt = optax.adamw(1e-3)

    def train(params, opt_state, batch):
        grads = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)
    )
    compiled = jax.jit(train).lower(
        params, jax.eval_shape(opt.init, params),
        {"tokens": _spec((2, 17))},
    ).compile()
    assert _instructions(compiled) == PARENT_INSTRUCTIONS["train"]
    assert _instructions(
        _llama_program("decode").compile()
    ) == PARENT_INSTRUCTIONS["decode"]
