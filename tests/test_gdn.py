"""``ops/gdn.py``: the gated delta rule's chunked (WY) scan and its
decode update (both backends; the Pallas kernel in interpret mode)
against the recurrence token by token.

Tolerances: float32 everywhere.  The chunked form reorders sums over a
sub-chunk of 64 rows and inverts a triangular system: 2e-5 against
outputs of order 0.5 and states of order 1; a bfloat16 state (3
significant digits) or a dropped ``alpha`` / ``beta`` would miss it by
1e-2 and more.  The decode update is the recurrence itself: 1e-6.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gdn

CHUNK_TOL = 2e-5
STEP_TOL = 1e-6


def _inputs(seed, bsz, t, heads, dk, dv):
    """Unit-norm ``k``, scaled unit-norm ``q``, ``beta`` over (0, 2) and
    ``alpha`` log-uniform over (0.04, 0.999), a random start state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (bsz, t, heads, dk))
    k = jax.random.normal(ks[1], (bsz, t, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (bsz, t, heads, dv))
    alpha = jnp.exp(
        jax.random.uniform(ks[3], (bsz, t, heads))
        * (np.log(0.999) - np.log(0.04)) + np.log(0.04)
    )
    beta = 2.0 * jax.random.uniform(ks[4], (bsz, t, heads))
    state = jax.random.normal(ks[5], (bsz, heads, dk, dv))
    return q, k, v, alpha, beta, state


@pytest.mark.parametrize("t", [64, 128, 100, 7, 65])
def test_chunk_scan_is_the_recurrence(t):
    """Lengths that are and are not multiples of the sub-chunk of 64."""
    args = _inputs(t, 2, t, 4, 24, 48)
    o_ref, s_ref = gdn.gdn_scan_reference(*args)
    o, s = gdn.gdn_chunk_scan(*args, chunk=64)
    assert o.shape == o_ref.shape and s.shape == s_ref.shape
    assert float(jnp.abs(o - o_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(s - s_ref).max()) < CHUNK_TOL
    # the inputs are of an order where the tolerance means something
    assert float(jnp.abs(o_ref).max()) > 0.1


@pytest.mark.parametrize("cut", [64, 37, 100])
def test_chunk_scan_carries_its_state_across_calls(cut):
    """A run split over two calls, the second starting from the state
    the first returned, is the run in one call."""
    q, k, v, alpha, beta, state = _inputs(3, 1, 150, 4, 24, 48)
    whole_o, whole_s = gdn.gdn_chunk_scan(q, k, v, alpha, beta, state)
    head = [x[:, :cut] for x in (q, k, v, alpha, beta)]
    tail = [x[:, cut:] for x in (q, k, v, alpha, beta)]
    o1, s1 = gdn.gdn_chunk_scan(*head, state)
    o2, s2 = gdn.gdn_chunk_scan(*tail, s1)
    o = jnp.concatenate([o1, o2], axis=1)
    assert float(jnp.abs(o - whole_o).max()) < CHUNK_TOL
    assert float(jnp.abs(s2 - whole_s).max()) < CHUNK_TOL


def test_padded_rows_advance_nothing():
    """``alpha == 1`` and ``beta == 0`` behind the real rows: the state
    after the padded run is the state after the real rows, whatever q,
    k and v hold there."""
    q, k, v, alpha, beta, state = _inputs(5, 1, 96, 4, 24, 48)
    real = 41
    alpha = alpha.at[:, real:].set(1.0)
    beta = beta.at[:, real:].set(0.0)
    _, padded = gdn.gdn_chunk_scan(q, k, v, alpha, beta, state)
    _, exact = gdn.gdn_scan_reference(
        *(x[:, :real] for x in (q, k, v, alpha, beta)), state
    )
    assert float(jnp.abs(padded - exact).max()) < CHUNK_TOL


def test_a_decay_that_underflowed_to_zero_forgets_and_stays_finite():
    """``alpha == 0`` (``exp`` of a large negative exponent underflows
    in float32): the state before it is gone, and nothing is NaN — the
    chunked form takes the decay's logarithm."""
    q, k, v, alpha, beta, state = _inputs(19, 1, 96, 4, 24, 48)
    alpha = alpha.at[:, 10].set(0.0).at[:, 70, 1].set(0.0)
    o_ref, s_ref = gdn.gdn_scan_reference(q, k, v, alpha, beta, state)
    o, s = gdn.gdn_chunk_scan(q, k, v, alpha, beta, state)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert float(jnp.abs(o - o_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(s - s_ref).max()) < CHUNK_TOL


@pytest.mark.parametrize("wrong", ["alpha", "beta", "bf16_state"])
def test_the_tolerance_sees_a_wrong_recurrence(wrong):
    """What the tolerance above is for: a dropped decay, a ``beta`` not
    doubled and a state rounded to bfloat16 each miss it by far."""
    q, k, v, alpha, beta, state = _inputs(7, 1, 128, 4, 24, 48)
    if wrong == "bf16_state":
        # a head that keeps what it holds (the published 0.999) shows it
        alpha = jnp.full_like(alpha, 0.999)
    o_ref, _ = gdn.gdn_scan_reference(q, k, v, alpha, beta, state)
    if wrong == "alpha":
        o, _ = gdn.gdn_chunk_scan(q, k, v, jnp.ones_like(alpha), beta, state)
    elif wrong == "beta":
        o, _ = gdn.gdn_chunk_scan(q, k, v, alpha, beta / 2, state)
    else:
        o, _ = gdn.gdn_chunk_scan(
            q, k, v, alpha, beta,
            state.astype(jnp.bfloat16).astype(jnp.float32),
        )
    assert float(jnp.abs(o - o_ref).max()) > 20 * CHUNK_TOL


# heads x dk x dv: all heads in one group of 192 columns; two groups of
# two heads (128 columns: the select between a group's heads); one head
# a group
SHAPES = [(4, 24, 48), (4, 16, 64), (2, 8, 128)]


@pytest.mark.parametrize("heads,dk,dv", SHAPES)
def test_state_layout_packs_heads_to_whole_lane_tiles(heads, dk, dv):
    groups, rows, width = gdn.state_shape(heads, dk, dv)
    assert rows == dk and groups * width == heads * dv
    assert width % 128 == 0 or groups == 1
    s = jax.random.normal(jax.random.PRNGKey(0), (3, heads, dk, dv))
    packed = gdn.pack_state(s)
    assert packed.shape == (3, groups, dk, width)
    # head ``g * p + i`` lies in group p at columns [i * dv, (i + 1) * dv)
    g = heads // groups
    assert bool((packed[1, 0, :, dv * (g - 1):] == s[1, g - 1]).all())
    assert bool((gdn.unpack_state(packed, heads) == s).all())


def test_the_published_state_is_held_without_padding():
    """30 heads of 96 x 192: pairs of heads, 384 = 3 lane tiles."""
    assert gdn.state_shape(30, 96, 192) == (15, 96, 384)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("heads,dk,dv", SHAPES)
def test_decode_update_is_one_step_of_the_recurrence(
        backend, heads, dk, dv):
    lanes, layers, layer = 5, 3, 1
    q, k, v, alpha, beta, _ = _inputs(11, lanes, 1, heads, dk, dv)
    slabs = jax.random.normal(
        jax.random.PRNGKey(9),
        (layers, lanes) + gdn.state_shape(heads, dk, dv),
    )
    real = jnp.array([True, False, True, True, False])
    o, new = gdn.gdn_decode_update(
        slabs, jnp.int32(layer), q[:, 0], k[:, 0], v[:, 0], alpha[:, 0],
        beta[:, 0], real, backend=backend,
    )
    o_ref, s_ref = gdn.gdn_scan_reference(
        q, k, v, alpha, beta, gdn.unpack_state(slabs[layer], heads)
    )
    got = gdn.unpack_state(new[layer], heads)
    assert float(jnp.abs(o[real] - o_ref[real, 0]).max()) < STEP_TOL
    assert float(jnp.abs(got[real] - s_ref[real]).max()) < STEP_TOL
    # an inactive lane: bitwise untouched, and it reads zeros
    assert bool((new[layer][~real] == slabs[layer][~real]).all())
    assert bool((o[~real] == 0).all())
    # other layers' slabs: bitwise untouched
    for other in (0, 2):
        assert bool((new[other] == slabs[other]).all())


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_decode_update_without_a_mask_advances_every_lane(backend):
    heads, dk, dv = 4, 16, 64
    q, k, v, alpha, beta, _ = _inputs(13, 3, 1, heads, dk, dv)
    slabs = jax.random.normal(
        jax.random.PRNGKey(2), (1, 3) + gdn.state_shape(heads, dk, dv)
    )
    o, new = gdn.gdn_decode_update(
        slabs, jnp.int32(0), q[:, 0], k[:, 0], v[:, 0], alpha[:, 0],
        beta[:, 0], backend=backend,
    )
    o_ref, s_ref = gdn.gdn_scan_reference(
        q, k, v, alpha, beta, gdn.unpack_state(slabs[0], heads)
    )
    assert float(jnp.abs(o - o_ref[:, 0]).max()) < STEP_TOL
    assert float(
        jnp.abs(gdn.unpack_state(new[0], heads) - s_ref).max()
    ) < STEP_TOL


def test_decode_steps_continue_a_chunk_scan():
    """Prefill by the chunked form, then decode token by token through
    the packed slab: the run in one recurrence."""
    heads, dk, dv = 4, 24, 48
    q, k, v, alpha, beta, _ = _inputs(17, 1, 80, heads, dk, dv)
    zero = jnp.zeros((1, heads, dk, dv))
    o_ref, s_ref = gdn.gdn_scan_reference(q, k, v, alpha, beta, zero)
    cut = 70
    _, s = gdn.gdn_chunk_scan(
        *(x[:, :cut] for x in (q, k, v, alpha, beta)), zero
    )
    slabs = gdn.pack_state(s)[None]  # [1 layer, 1 lane, ...]
    for t in range(cut, 80):
        o, slabs = gdn.gdn_decode_update(
            slabs, jnp.int32(0), q[:, t], k[:, t], v[:, t], alpha[:, t],
            beta[:, t], backend="pallas",
        )
        assert float(jnp.abs(o - o_ref[:, t]).max()) < CHUNK_TOL
    assert float(
        jnp.abs(gdn.unpack_state(slabs[0], heads) - s_ref).max()
    ) < CHUNK_TOL


def _hard_case(name):
    """Inputs on which the inverse of a sub-chunk's system ``I + A`` is
    far from ``I - A``: what a prompt that repeats a token, a padded or
    gated-off row, a short or ragged run and a forgotten past make of
    it."""
    t = {"run_1": 1, "run_63": 63, "run_65": 65, "run_256": 256}.get(
        name, 64
    )
    q, k, v, alpha, beta, state = _inputs(23, 1, t, 4, 24, 48)
    if name == "identical_keys":
        # every row reflects the state about ONE key: A[t, j] == 2 for
        # every j < t, the inverse's entries alternate +-2 and powers of
        # A grow like 2 ** n * binomials before they cancel
        k = jnp.broadcast_to(k[:, :1], k.shape)
        alpha, beta = jnp.ones_like(alpha), jnp.full_like(beta, 2.0)
        # nothing decays and the state's component along the key walks
        # by 2 v a token: a sixteenth of v (2 * sqrt(64)) keeps outputs
        # and states of the order the tolerance is stated for
        v, state = v / 16, state / 16
    elif name == "beta_zero_rows":
        beta = beta.at[:, 5:9].set(0.0).at[:, 30, 2].set(0.0)
        beta = beta.at[:, 63].set(0.0)
    elif name == "alpha_underflow_mid":
        alpha = alpha.at[:, 31].set(0.0).at[:, 40, 1].set(1e-42)
    return q, k, v, alpha, beta, state


@pytest.mark.parametrize("name", [
    "identical_keys", "beta_zero_rows", "alpha_underflow_mid",
    "run_1", "run_63", "run_64", "run_65", "run_256",
])
def test_chunk_scan_inverts_its_hard_systems(name):
    """The WY system is inverted by products (forward substitution in
    blocks): the recurrence, at the tolerance of a triangular solve, on
    the inputs where a shorter product of powers of ``A`` would not
    be."""
    args = _hard_case(name)
    o_ref, s_ref = gdn.gdn_scan_reference(*args)
    o, s = jax.jit(gdn.gdn_chunk_scan)(*args)
    assert o.shape == o_ref.shape and s.shape == s_ref.shape
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert float(jnp.abs(o - o_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(s - s_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(o_ref).max()) > 0.1


def test_the_hard_systems_see_a_product_of_powers(monkeypatch):
    """What the repeated keys are there for: ``(I - A)(I + A^2)(I +
    A^4)...(I + A^32)`` IS the inverse (``A`` is nilpotent) and passes
    on random keys, but its powers of ``A`` outgrow float32's digits
    before they cancel once keys repeat."""
    def product_of_powers(system):
        eye = jnp.eye(system.shape[-1], dtype=system.dtype)
        mm = jax.lax.Precision.HIGHEST
        inv, power = eye - (system - eye), system - eye
        for _ in range(5):
            power = jnp.matmul(power, power, precision=mm)
            inv = jnp.matmul(inv, eye + power, precision=mm)
        return inv

    monkeypatch.setattr(gdn, "_unit_lower_inverse", product_of_powers)
    for name, passes in (("run_64", True), ("identical_keys", False)):
        args = _hard_case(name)
        o_ref, _ = gdn.gdn_scan_reference(*args)
        # a function of its own: ``jit`` must trace the patched inverse
        o, _ = jax.jit(lambda *a: gdn.gdn_chunk_scan(*a))(*args)
        assert (float(jnp.abs(o - o_ref).max()) < CHUNK_TOL) == passes
