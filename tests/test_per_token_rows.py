"""A finished request's per-position rows on their way out (ISSUE 62):
laid in the lane's own arrays as steps commit, written into the ring's
slot in place, copied out of it once — and bit for bit what the parent
(8a64990: ``_per_token_rows`` over ``sl.rows``, a padded temporary, the
slot copied whole) delivered.  Two witnesses: an oracle rebuilt here
the parent's way from what the step programs returned, position by
position, and digests pinned from the parent's own run of the same
scenarios (``python tests/test_per_token_rows.py`` with the parent's
tree on ``PYTHONPATH`` prints them).  On the tiny DeepSeek-V3.2
(``selection`` and ``experts``) and the tiny Keye-VL2 (``experts``
alone)."""

import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import tiny_families as T  # noqa: E402

FAMILIES = ("deepseek_v32", "keye_vl2")
FACTORIES = {
    "deepseek_v32": "deepseek_v32_factory",
    "keye_vl2": "keye_vl2_factory",
}
#: the geometry of the families' own test files: the step programs are
#: those they compiled
SCHED = dict(
    max_slots=3, block_size=4, num_blocks=48, max_seq_len=64,
    prefill_chunk=12, temperature=1.0,
)
#: one lane: the replica and the scheduler beside it run the same
#: programs on the same values whatever the arrivals' timing
ENGINE = dict(
    max_slots=1, block_size=4, num_blocks=20, max_seq_len=64,
    prefill_chunk=12, temperature=1.0,
)
#: (prompt length, max_new, seed): the first fills ``max_seq_len``; the
#: per-position ring has four slots, so the fifth lands in the slot the
#: first left 64 rows in and brings 10
ENGINE_REQUESTS = (
    (41, 23, 0), (7, 3, 1), (12, 4, 2), (9, 3, 3), (8, 2, 4), (30, 9, 5),
)

#: what the parent's run of each scenario gave, ``(tokens and logprobs,
#: rows)``: sha256 of the arrays' bytes in request order.  The first is
#: the model's own arithmetic on this machine: where it reads otherwise
#: the pin says nothing about the rows and the case is skipped.
PINNED = {
    "deepseek_v32.engine": (
        "41962bc51bd860a59b7f4f2b", "851315d2a081b0ee9959b4c3",
    ),
    "deepseek_v32.eos": (
        "b24c135a1e0753d43410983b", "659f976ffe0d01155537ad4c",
    ),
    "deepseek_v32.mixed": (
        "c265cf5cef6976a41eae19f1", "9238aa0e7653d31f9bc545e5",
    ),
    "deepseek_v32.preempted": (
        "9d01abc6643a2375f82b4bc6", "ce2dad53f8d0351d276cd53c",
    ),
    "keye_vl2.engine": (
        "df20aa90c775f6d639c8299f", "1a6a103b7d2b66a60513e804",
    ),
    "keye_vl2.eos": (
        "9dbbada958cb27f1eace64bc", "bc844a03007e61299a81521d",
    ),
    "keye_vl2.mixed": (
        "d2f07fb2741cc800a006a3f4", "ce132d140e7aa865a64aae80",
    ),
    "keye_vl2.preempted": (
        "5d2b6d4a8cfe7ab0cac103dc", "3fe2ae627dc1fb53d799c43e",
    ),
}


def _prompts(lengths, vocab, seed=1):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths
    ]


def _vocab(family):
    return T.config(family)["vocab_size"]


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:24]


def digests(results):
    """``(model, rows)`` of ``{req_id: (tokens, logprobs, per_token)}``."""
    model, rows = [], []
    for rid in sorted(results):
        tokens, logprobs, per_token = results[rid]
        model += [tokens, logprobs]
        rows += [per_token[name] for name in sorted(per_token)]
    return _digest(model), _digest(rows)


# ------------------------------------------------- the parent's way


def tap(sch):
    """Record what ``sch``'s step programs return a position, ``{(req_id,
    position): {name: row}}`` as dispatched, the later of two writes
    kept — a resumed lane computes its prompt and tail again."""
    seen = {}
    mw = sch.sched.max_blocks_per_seq + sch._wtables.shape[1]
    decode, chunk, last = (
        sch._decode_jit, sch._prefill_jit, sch._prefill_last_jit
    )

    def decode_tap(params, pool, toks, packed, keys):
        out = decode(params, pool, toks, packed, keys)
        rows = {n: np.asarray(a) for n, a in out[-1].items()}
        for slot in np.flatnonzero(packed[:, mw + 1]):
            key = (sch._slots[slot].req.req_id, int(packed[slot, mw]))
            seen[key] = {n: a[slot].copy() for n, a in rows.items()}
        return out

    def prefill_tap(program, lead):
        def call(*args):
            out = program(*args)
            start, slot, real = (int(a) for a in args[lead + 2:lead + 5])
            rows = {n: np.asarray(a) for n, a in out[-1].items()}
            req_id = sch._slots[slot].req.req_id
            for j in range(real):
                seen[(req_id, start + j)] = {
                    n: a[j].copy() for n, a in rows.items()
                }
            return out
        return call

    sch._decode_jit = decode_tap
    sch._prefill_jit = prefill_tap(chunk, 2)
    sch._prefill_last_jit = prefill_tap(last, 4)
    return seen


def the_parents_rows(sch, seen, result):
    """``_per_token_rows`` as the parent had it: an array of -1 / NaN a
    name, the request's own length, and every computed position's row
    copied in — all but the last's, which no step that was committed
    computed."""
    n = result.tokens.size
    out = {}
    for name, (shape, dtype) in sch.per_token.items():
        dt = np.dtype(dtype)
        full = np.full(
            (n,) + tuple(shape), -1 if dt.kind == "i" else np.nan, dt
        )
        for pos in range(n - 1):
            full[pos] = seen[(result.req_id, pos)][name]
        out[name] = full
    return out


# --------------------------------------------------- the scenarios


def _scheduler(family, **overrides):
    return T.scheduler(
        T.parts(family, 64), dict(SCHED, **overrides),
        T.params(family, 2**31 + 42),
    )


def _run(sch, prompts, max_new, steps_then=None):
    seen = tap(sch)
    for i, (p, new) in enumerate(zip(prompts, max_new)):
        sch.submit(p, max_new=new, seed=i)
    out = []
    if steps_then is not None:
        steps, then = steps_then
        for _ in range(steps):
            out.extend(sch.step())
        then(sch)
    out.extend(sch.run())
    return sch, seen, {r.req_id: r for r in out}


def mixed(family):
    """Six requests on three lanes, slots and blocks reused, prompts of
    one to four chunks; the third fills ``max_seq_len``."""
    return _run(
        _scheduler(family),
        _prompts((30, 7, 41, 12, 25, 18), _vocab(family)),
        (9, 10, 23, 12, 13, 14),
    )


def eos(family, calm):
    """The same prompts under an ``eos_id`` that ends the first request
    early (its fifth new token in ``calm``, the run without): a lane
    that leaves with a step in flight, whose row is dropped."""
    first = calm[0]
    eos_id = int(first.tokens[first.tokens.size - first.new_tokens + 4])
    return _run(
        _scheduler(family, eos_id=eos_id),
        _prompts((30, 7, 41, 12, 25, 18), _vocab(family)),
        (9, 10, 23, 12, 13, 14),
    )


def preempted(family):
    """A lane evicted while it decodes, re-admitted with its tail: its
    prompt's and tail's rows are the second prefill's."""
    def evict(sch):
        sch._preempt(next(
            i for i, sl in enumerate(sch._slots) if sl.phase == "decode"
        ))

    return _run(
        _scheduler(family),
        _prompts((19, 24, 17), _vocab(family), seed=9),
        (12, 13, 14),
        steps_then=(8, evict),
    )


SCENARIOS = ("eos", "mixed", "preempted")


def scenarios(family):
    """Each scenario's ``(scheduler, tapped rows, results)``."""
    import jax

    with jax.default_matmul_precision("highest"):
        calm = mixed(family)
        return {
            "mixed": calm,
            "eos": eos(family, calm[2]),
            "preempted": preempted(family),
        }


def _as_results(served):
    return {
        rid: (r.tokens, r.logprobs, r.per_token)
        for rid, r in served.items()
    }


def _factory_parts(family):
    from dlrover_tpu.rl import generation_service as gs

    return getattr(gs, FACTORIES[family])(**T.kwargs(family, 64))


def beside_the_engine(family):
    """``ENGINE_REQUESTS`` through a scheduler in this process, one lane
    as the replica has: what the replica's own scheduler hands over."""
    served = _factory_parts(family)
    sch = T.scheduler(served, ENGINE, served["params_template_fn"]())
    prompts = _prompts(
        [n for n, _, _ in ENGINE_REQUESTS], _vocab(family), seed=3
    )
    for p, (_, new, seed) in zip(prompts, ENGINE_REQUESTS):
        sch.submit(p, max_new=new, seed=seed)
    return {r.req_id: r for r in sch.run()}


def through_the_engine(family, events_path=None):
    """``ENGINE_REQUESTS`` through a one-replica ``ServingEngine``:
    ``{submit order: the dispatcher's result}``."""
    from dlrover_tpu.rl.generation_service import ServingEngine

    with pytest.MonkeyPatch.context() as mp:
        if events_path is not None:
            mp.setenv("DLROVER_TPU_EVENTS_FILE", str(events_path))
        eng = ServingEngine(
            factory="dlrover_tpu.rl.generation_service:"
            + FACTORIES[family],
            factory_kwargs=T.kwargs(family, 64),
            max_new_tokens=4,
            name=f"rows-{family[:4]}-{os.getpid()}",
            num_replicas=1,
            capture_logprobs=True,
            **ENGINE,
        )
        try:
            prompts = _prompts(
                [n for n, _, _ in ENGINE_REQUESTS], _vocab(family), seed=3
            )
            ids = [
                eng.submit(p, max_new=new, seed=seed)
                for p, (_, new, seed) in zip(prompts, ENGINE_REQUESTS)
            ]
            return {
                i: eng.result(rid, timeout=300)
                for i, rid in enumerate(ids)
            }
        finally:
            eng.close()


def _engine_results(served):
    return {
        i: (r["tokens"], r["logprobs"], r["per_token"])
        for i, r in served.items()
    }


# ------------------------------------------------------- the tests


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    return request.param


@pytest.fixture(scope="module")
def runs(family):
    """The scenarios, served once a family."""
    return scenarios(family)


def _against_the_pin(key, results):
    if key not in PINNED:
        pytest.fail(f"no pin for {key}: run this file on the parent")
    model, rows = digests(results)
    if model != PINNED[key][0]:
        pytest.skip(
            "the model's own tokens and logprobs read otherwise here "
            "than where the parent's rows were pinned"
        )
    assert rows == PINNED[key][1]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_a_result_carries_the_rows_the_parents_way(runs, scenario):
    sch, seen, served = runs[scenario]
    assert served
    for r in served.values():
        want = the_parents_rows(sch, seen, r)
        assert sorted(r.per_token) == sorted(want)
        for name, rows in want.items():
            got = r.per_token[name]
            assert got.dtype == rows.dtype and got.shape == rows.shape
            assert got.tobytes() == rows.tobytes(), (r.req_id, name)


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_a_result_carries_the_rows_pinned_from_the_parent(
        runs, family, scenario):
    _, _, served = runs[scenario]
    _against_the_pin(f"{family}.{scenario}", _as_results(served))


def test_the_scenarios_are_what_they_say(runs):
    sch, _, served = runs["mixed"]
    full = served[2]
    assert full.tokens.size == SCHED["max_seq_len"]
    for r in served.values():
        for rows in r.per_token.values():
            # the last position alone was never computed
            assert (rows[-1] == -1).all() and (rows[:-1] != -1).any(-1).all()
    _, _, ended = runs["eos"]
    assert ended[0].finish_reason == "eos" and ended[0].new_tokens == 5
    assert runs["eos"][0].stats()["overrun_tokens"] > 0
    evicted = runs["preempted"][0]
    assert evicted.preemptions == 1
    # nothing of a chunk's stays behind on the device or in a list
    for sched, _, _ in runs.values():
        assert sched._chunk_rows == [] and sched.idle


def test_a_lane_owns_rows_of_its_requests_own_length(family):
    """Untouched memory of ``prompt + max_new`` rows from admission, a
    chunk's rows in it one commit after the chunk and the device's let
    go of, the result a view of it."""
    import jax

    with jax.default_matmul_precision("highest"):
        sch = _scheduler(family)
        prompt = _prompts((30,), _vocab(family))[0]
        sch.submit(prompt, max_new=5, seed=0)
        sch.step()  # admitted; the first chunk of three dispatched
        sl = next(s for s in sch._slots if s.req is not None)
        for name, (shape, dtype) in sch.per_token.items():
            assert sl.rows[name].shape == (35,) + tuple(shape)
            assert sl.rows[name].dtype == np.dtype(dtype)
        assert len(sch._chunk_rows) == 1 and sl.rows_upto == 0
        sch.step()  # the second chunk; the first one's rows landed
        assert len(sch._chunk_rows) == 1 and sl.rows_upto == 12
        held = dict(sl.rows)
        (result,) = sch.run()
        assert sch._chunk_rows == []
        for name, rows in result.per_token.items():
            assert rows.shape[0] == 35 and rows.base is held[name]


@pytest.fixture(scope="module")
def engine(family, tmp_path_factory):
    socks = tmp_path_factory.mktemp("sk")
    events = tmp_path_factory.mktemp("rows") / "events.jsonl"
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLROVER_TPU_SOCKET_DIR", str(socks))
        return through_the_engine(family, events), events


@pytest.mark.heavy
def test_the_dispatcher_hands_back_the_schedulers_rows(family, engine):
    """Bit for bit what a scheduler beside it carries in its
    ``GenResult`` — the request of ``max_seq_len`` whole, and the
    shorter one that follows it in the same slot with nothing of the
    rows it left there."""
    got, _ = engine
    want = beside_the_engine(family)
    assert sorted(got) == sorted(want) == list(range(len(ENGINE_REQUESTS)))
    for i, (plen, new, _) in enumerate(ENGINE_REQUESTS):
        assert (got[i]["tokens"] == want[i].tokens).all()
        assert got[i]["tokens"].size == plen + new
        assert sorted(got[i]["per_token"]) == sorted(want[i].per_token)
        for name, rows in want[i].per_token.items():
            back = got[i]["per_token"][name]
            assert back.dtype == rows.dtype and back.shape == rows.shape
            assert back.tobytes() == rows.tobytes(), (i, name)
            assert (back[-1] == -1).all()


@pytest.mark.heavy
def test_the_dispatcher_hands_back_the_rows_pinned_from_the_parent(
        family, engine):
    got, _ = engine
    _against_the_pin(f"{family}.engine", _engine_results(got))


@pytest.mark.heavy
def test_a_reply_counts_one_copy_of_the_requests_own_rows(family, engine):
    from dlrover_tpu.observability import events as ev

    got, path = engine
    replies = [
        s for s in ev.pair_spans(ev.read_events(str(path)))
        if s["phase"] == "reply"
    ]
    assert len(replies) == len(ENGINE_REQUESTS)
    own = sorted(
        sum(a.nbytes for a in r["per_token"].values())
        for r in got.values()
    )
    assert sorted(s["labels"]["per_token_bytes"] for s in replies) == own
    assert sorted(s["labels"]["copied_bytes"] for s in replies) == own
    assert min(own) > 0


# ------------------------------------------- the ring's reserve / publish


@pytest.fixture
def ring_pair(tmp_path_factory, monkeypatch):
    """``(reader, writer)`` of one two-slot per-position ring, as the
    dispatcher makes it and the replica attaches."""
    from dlrover_tpu.rl import generation_service as gs

    monkeypatch.setenv(
        "DLROVER_TPU_SOCKET_DIR", str(tmp_path_factory.mktemp("sk"))
    )
    name = f"rsv-{os.getpid()}"
    spec = gs._per_token_spec(8, {"experts": ((2, 2), "int32")})
    reader = gs._Ring(name, spec=spec, num_slots=2, create=True)
    writer = gs._Ring(name)
    yield reader, writer
    writer.close()
    reader.close(unlink=True)


def test_a_reserved_slot_is_not_visible_until_published(ring_pair):
    reader, writer = ring_pair
    slot = writer.reserve()
    assert slot is not None and slot["experts"].shape == (8, 2, 2)
    slot["meta"][:] = (7, 3)
    slot["experts"][:3] = 5
    assert reader.try_get() is None and reader.peek() is None
    writer.publish()
    seen = reader.peek()
    assert seen is not None and list(seen["meta"]) == [7, 3]
    assert (seen["experts"][:3] == 5).all()
    # in place: the reader's view is the writer's memory
    assert np.shares_memory(seen["experts"], reader._ring.slot_views(0)[
        "experts"
    ])
    reader.release()
    assert reader.peek() is None
    # the freed slot comes round again, what was left in it with it:
    # a shorter message says how much of it is its own
    writer.reserve()["meta"][:] = (8, 1)
    writer.publish()
    slot = writer.reserve()
    assert (slot["experts"][:3] == 5).all()
    slot["meta"][:] = (9, 1)
    slot["experts"][:1] = 6
    writer.publish()
    assert int(reader.try_get()["meta"][0]) == 8
    msg = reader.try_get()
    assert list(msg["meta"]) == [9, 1]
    assert (msg["experts"][:1] == 6).all()


def test_the_rings_maker_touches_every_page_of_it(
        tmp_path_factory, monkeypatch):
    """Slots written in place at a message's own length: no page of the
    segment is left for the writer to fault in under load (tmpfs counts
    a file's touched pages as its blocks; a ring made without ``touch``
    holds its header's page alone)."""
    from dlrover_tpu.data import shm_dataloader as sd
    from dlrover_tpu.rl import generation_service as gs

    monkeypatch.setenv(
        "DLROVER_TPU_SOCKET_DIR", str(tmp_path_factory.mktemp("sk"))
    )
    spec = gs._per_token_spec(4096, {"experts": ((2, 2), "int32")})
    served = gs._Ring(f"tch-{os.getpid()}", spec=spec, num_slots=2,
                      create=True)
    bare = sd._ShmRing(f"bare-{os.getpid()}", spec, 2, create=True)

    def held(ring):
        path = f"/dev/shm/{ring.shm.name.lstrip('/')}"
        return os.stat(path).st_blocks * 512

    try:
        assert held(served._ring) >= 2 * spec.slot_bytes > 100_000
        assert held(bare) < spec.slot_bytes
    finally:
        served.close(unlink=True)
        bare.close(unlink=True)


def test_a_full_ring_makes_reserve_wait_and_give_up_as_try_put_does(
        ring_pair):
    reader, writer = ring_pair
    msg = {
        "meta": np.asarray([1, 8], np.int64),
        "experts": np.zeros((8, 2, 2), np.int32),
    }
    assert writer.try_put(msg) and writer.try_put(msg)
    t0 = time.monotonic()
    assert writer.reserve(timeout=0.2) is None
    assert 0.2 <= time.monotonic() - t0 < 2.0
    assert not writer.try_put(msg, timeout=0.05)
    assert writer.reserve() is None  # no wait at all
    assert reader.try_get() is not None
    assert writer.reserve(timeout=0.2) is not None
    writer.publish()
    assert reader.try_get() is not None and reader.try_get() is not None
    assert reader.try_get() is None


if __name__ == "__main__":
    # the pins, from whatever tree is first on PYTHONPATH (the parent's)
    import tempfile

    pins = {}
    for fam in FAMILIES:
        for scenario, (_, _, served) in scenarios(fam).items():
            pins[f"{fam}.{scenario}"] = digests(_as_results(served))
        with tempfile.TemporaryDirectory(prefix="sk") as socks:
            os.environ["DLROVER_TPU_SOCKET_DIR"] = socks
            pins[f"{fam}.engine"] = digests(
                _engine_results(through_the_engine(fam))
            )
    print(json.dumps(pins, indent=4, sort_keys=True))
