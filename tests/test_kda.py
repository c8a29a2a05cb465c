"""``ops/kda.py``: Kimi Delta Attention's chunked (WY) scan and its
decode update (both backends; the Pallas kernel in interpret mode)
against the recurrence token by token, and the recurrence against
``ops/gdn.py``'s where a head's channels share one decay.

Tolerances: float32 everywhere.  The chunked form reorders sums over a
sub-chunk of 64 rows, exponentiates the decay in blocks of 16 and
inverts a triangular system: 2e-5 against outputs of order 0.5 and
states of order 1; a decay taken a head (the scalar gate of
``ops/gdn.py``) misses it by 1e-2 and more
(``test_a_decay_a_head_is_another_function``).  The decode update is
the recurrence itself: 1e-6.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gdn, kda

CHUNK_TOL = 2e-5
STEP_TOL = 1e-6


def _inputs(seed, bsz, t, heads, dk, dv, low=0.04):
    """Unit-norm ``k``, scaled unit-norm ``q``, ``beta`` over (0, 1) and
    ``alpha`` log-uniform over (``low``, 0.999) a CHANNEL, a random
    start state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (bsz, t, heads, dk))
    k = jax.random.normal(ks[1], (bsz, t, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (bsz, t, heads, dv))
    alpha = jnp.exp(
        jax.random.uniform(ks[3], (bsz, t, heads, dk))
        * (np.log(0.999) - np.log(low)) + np.log(low)
    )
    beta = jax.random.uniform(ks[4], (bsz, t, heads))
    state = jax.random.normal(ks[5], (bsz, heads, dk, dv))
    return q, k, v, alpha, beta, state


@pytest.mark.parametrize("t,chunk", [
    (64, 64), (100, 64), (7, 64), (40, 16), (50, 8),
])
def test_chunk_scan_is_the_recurrence(t, chunk):
    """Lengths that are and are not multiples of the sub-chunk, and
    sub-chunks of one block and of fewer tokens than a block."""
    args = _inputs(t, 2, t, 3, 24, 16)
    o_ref, s_ref = kda.kda_scan_reference(*args)
    o, s = kda.kda_chunk_scan(*args, chunk=chunk)
    assert o.shape == o_ref.shape and s.shape == s_ref.shape
    assert float(jnp.abs(o - o_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(s - s_ref).max()) < CHUNK_TOL
    # the inputs are of an order where the tolerance means something
    assert float(jnp.abs(o_ref).max()) > 0.1


@pytest.mark.parametrize("cut", [64, 37])
def test_chunk_scan_carries_its_state_across_calls(cut):
    q, k, v, alpha, beta, state = _inputs(3, 1, 150, 3, 24, 16)
    whole_o, whole_s = kda.kda_chunk_scan(q, k, v, alpha, beta, state)
    head = [x[:, :cut] for x in (q, k, v, alpha, beta)]
    tail = [x[:, cut:] for x in (q, k, v, alpha, beta)]
    o1, s1 = kda.kda_chunk_scan(*head, state)
    o2, s2 = kda.kda_chunk_scan(*tail, s1)
    assert float(
        jnp.abs(jnp.concatenate([o1, o2], 1) - whole_o).max()
    ) < CHUNK_TOL
    assert float(jnp.abs(s2 - whole_s).max()) < CHUNK_TOL


def test_a_padded_tail_advances_nothing():
    """Tokens with ``alpha == 1`` and ``beta == 0`` (a prefill chunk's
    padding) leave the state where the real ones put it."""
    q, k, v, alpha, beta, state = _inputs(5, 1, 96, 3, 24, 16)
    real = 41
    valid = jnp.arange(96) < real
    alpha = jnp.where(valid[None, :, None, None], alpha, 1.0)
    beta = jnp.where(valid[None, :, None], beta, 0.0)
    _, padded = kda.kda_chunk_scan(q, k, v, alpha, beta, state)
    _, cut = kda.kda_scan_reference(
        *(x[:, :real] for x in (q, k, v, alpha, beta)), state
    )
    assert float(jnp.abs(padded - cut).max()) < CHUNK_TOL


@pytest.mark.parametrize("low", [1e-3, 1e-30])
def test_a_strong_decay_stays_finite_and_equal(low):
    """A decay down to ``low`` a token: over a 64-row sub-chunk the
    naive product ``(k exp(G - G_0)) (k exp(G_0 - G))^T`` needs
    ``exp(+63 * |log low|)`` = 1e189 and more, which float32 does not
    hold (1e-30: a decay that all but underflows to 0).  The blocked
    form raises nothing to a positive power."""
    args = _inputs(11, 1, 128, 2, 24, 16, low=low)
    q, k, v, alpha, beta, state = args
    naive = jnp.exp(-jnp.cumsum(jnp.log(alpha[:, :64]), axis=1))
    assert not bool(jnp.isfinite(naive).all())  # the case is the case
    o_ref, s_ref = kda.kda_scan_reference(*args)
    o, s = kda.kda_chunk_scan(*args)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert float(jnp.abs(o - o_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(s - s_ref).max()) < CHUNK_TOL


def test_an_underflowed_decay_is_finite():
    """``alpha`` exactly 0 in some channels (``exp`` of a large negative
    gate on the chip): the log is clamped, nothing is NaN."""
    q, k, v, alpha, beta, state = _inputs(13, 1, 70, 2, 24, 16)
    alpha = alpha.at[:, ::5, :, ::3].set(0.0)
    o_ref, s_ref = kda.kda_scan_reference(q, k, v, alpha, beta, state)
    o, s = kda.kda_chunk_scan(q, k, v, alpha, beta, state)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert float(jnp.abs(o - o_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(s - s_ref).max()) < CHUNK_TOL


def test_channels_that_share_one_decay_are_the_scalar_gate():
    """Where a head's channels share one decay the rule is
    ``ops/gdn.py``'s: ``alpha (I - beta k k^T) S``."""
    q, k, v, alpha, beta, state = _inputs(17, 2, 90, 3, 24, 16)
    one = alpha[..., 0]
    shared = jnp.broadcast_to(one[..., None], alpha.shape)
    o_g, s_g = gdn.gdn_scan_reference(q, k, v, one, beta, state)
    for scan in (kda.kda_scan_reference, kda.kda_chunk_scan):
        o, s = scan(q, k, v, shared, beta, state)
        assert float(jnp.abs(o - o_g).max()) < CHUNK_TOL
        assert float(jnp.abs(s - s_g).max()) < CHUNK_TOL


def test_a_decay_a_head_is_another_function():
    """The control of the tolerances above: a head's decays replaced by
    their mean (the scalar gate) moves the output by orders more."""
    q, k, v, alpha, beta, state = _inputs(19, 1, 64, 3, 24, 16)
    o, _ = kda.kda_chunk_scan(q, k, v, alpha, beta, state)
    mean = jnp.broadcast_to(alpha.mean(-1, keepdims=True), alpha.shape)
    o_mean, _ = kda.kda_chunk_scan(q, k, v, mean, beta, state)
    assert float(jnp.abs(o - o_mean).max()) > 1000 * CHUNK_TOL


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_decode_update_is_one_step_of_the_recurrence(backend):
    """Three slabs, the middle one advanced; one lane inactive: its
    state bitwise as given, its output zeros; the other slabs
    untouched."""
    lanes, heads, dk, dv = 5, 4, 16, 128
    q, k, v, alpha, beta, _ = _inputs(23, lanes, 1, heads, dk, dv)
    slabs = jax.random.normal(
        jax.random.PRNGKey(29), (3, lanes, heads, dk, dv)
    )
    real = jnp.array([True, True, False, True, True])
    o_ref, s_ref = kda.kda_scan_reference(q, k, v, alpha, beta, slabs[1])
    o, out = kda.kda_decode_update(
        slabs, jnp.int32(1), q[:, 0], k[:, 0], v[:, 0], alpha[:, 0],
        beta[:, 0], real, backend,
    )
    keep = np.asarray(real)
    assert float(jnp.abs(o[keep] - o_ref[keep, 0]).max()) < STEP_TOL
    assert float(jnp.abs(out[1][keep] - s_ref[keep]).max()) < STEP_TOL
    assert bool((out[1][2] == slabs[1][2]).all())
    assert bool((o[2] == 0).all())
    assert bool((out[0] == slabs[0]).all()) and bool(
        (out[2] == slabs[2]).all()
    )


def test_decode_steps_then_a_chunk_are_one_run():
    """Decode updates token by token, then a chunk scan from the state
    they left: the run in one scan."""
    q, k, v, alpha, beta, state = _inputs(31, 2, 40, 3, 24, 16)
    slabs = state[None]
    outs = []
    for i in range(8):
        o, slabs = kda.kda_decode_update(
            slabs, jnp.int32(0), q[:, i], k[:, i], v[:, i], alpha[:, i],
            beta[:, i], None, "jnp",
        )
        outs.append(o)
    o2, s2 = kda.kda_chunk_scan(
        *(x[:, 8:] for x in (q, k, v, alpha, beta)), slabs[0]
    )
    o_ref, s_ref = kda.kda_scan_reference(q, k, v, alpha, beta, state)
    got = jnp.concatenate([jnp.stack(outs, 1), o2], 1)
    assert float(jnp.abs(got - o_ref).max()) < CHUNK_TOL
    assert float(jnp.abs(s2 - s_ref).max()) < CHUNK_TOL
