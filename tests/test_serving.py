"""Serving tier: paged KV cache, continuous-batching decode, elastic
replicas (ISSUE 13).

The acceptance pins:
* paged-cache decode is BIT-IDENTICAL to the dense contiguous-cache
  oracle (0 tolerance, through interleaved joins/leaves and ragged
  final blocks);
* the ``decode_step`` collective budget holds on the compiled
  tensor-parallel program with zero partitioner insertions;
* allocator admit/evict/fragmentation invariants;
* cache state round-trips through the existing checkpoint layer;
* request retry/timeout ride the resilience taxonomy without dropping
  deterministic outputs.
"""

import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chainermn_tpu as cmn
from chainermn_tpu.models.transformer import TransformerLM, generate
from chainermn_tpu.ops.pallas_attention import (
    flash_decode,
    paged_decode_reference,
)
from chainermn_tpu.serving.batcher import ContinuousBatcher, Request
from chainermn_tpu.serving.decode import DecodeEngine, engine_from_trained
from chainermn_tpu.serving.kv_cache import (
    CacheAdmissionError,
    KVExport,
    NULL_PAGE,
    PagedKVCache,
    PrefixMatch,
    pages_needed,
    reshard_kv_state,
)
from chainermn_tpu.serving.speculative import SpeculativeBatcher
from chainermn_tpu.serving.replica import (
    DecodeReplica,
    RequestJournal,
    claim,
)
from chainermn_tpu.serving.disagg import (
    DisaggDecodeReplica,
    PrefillReplica,
    load_handoff,
    pack_handoff,
    publish_handoff,
    transfer_kv,
    unpack_handoff,
)
from chainermn_tpu.resilience.fault_injection import (
    FaultSpec,
    inject_faults,
)


VOCAB, D, HEADS, LAYERS, MAXLEN = 64, 32, 4, 2, 64


def _cache(capacity=3, page_size=4, pages_per_slot=4, num_pages=None):
    return PagedKVCache(n_layers=LAYERS, n_heads=HEADS,
                        d_head=D // HEADS, capacity=capacity,
                        page_size=page_size,
                        pages_per_slot=pages_per_slot,
                        num_pages=num_pages)


def _shared_prompts(n, seed=17, page=8):
    """Prompts over one page-aligned shared system prefix + unique
    tails — the high-overlap mix prefix sharing exists for."""
    rng = np.random.RandomState(seed)
    head = rng.randint(0, VOCAB, page).tolist()
    return [head + rng.randint(0, VOCAB, 2 + rng.randint(3)).tolist()
            for _ in range(n)]


def _aligned_copy(a, align=64):
    """``a`` in a buffer aligned the way the CPU backend wants before it
    takes a host array without copying it."""
    raw = np.zeros(a.nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def _first_fresh_token(generated):
    """``(eos, n)``: the first generated token past the first that no
    earlier one equals, and how many tokens a request stopping on it
    emits (greedy decode of a random model repeats itself)."""
    for i in range(1, len(generated)):
        if generated[i] not in generated[:i]:
            return generated[i], i + 1
    raise AssertionError(f"no fresh token in {generated}")


#: Two programs for one logits row (another batch shape, the speculative
#: verify step) agree to float32 rounding, not to the bit: where the
#: model's top logits lie closer than this, which token a greedy decode
#: takes is rounding's to say.
GREEDY_TIE = 1e-5


def _assert_same_greedy(lm, got, want):
    """``got`` / ``want``: greedy decodes (prompt + tokens) of one prompt
    by two different programs.  Equal token for token, or parting at a
    step where the model's own float32 logits for the two tokens both
    lie within ``GREEDY_TIE`` of the largest; what follows such a tie is
    another sequence and is not compared."""
    if got == want:
        return
    parts = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    assert parts, f"one is a prefix of the other: {got} vs {want}"
    i = parts[0]
    model, params = lm
    row = np.asarray(model.apply(
        params, jnp.asarray([want[:i]], jnp.int32),
        rngs={"dropout": jax.random.PRNGKey(0)})[0, -1], np.float32)
    worse = min(row[got[i]], row[want[i]])
    assert row.max() - worse <= GREEDY_TIE, (
        f"decodes part at {i} ({got[i]} vs {want[i]}) where the logits "
        f"are {row.max() - worse:.3g} apart: {got} vs {want}")


def _draft_engine(eng, seed=7, zero=False):
    """A half-width 1-layer draft engine built to ``eng``'s exact cache
    geometry (the SpeculativeBatcher contract)."""
    dm = TransformerLM(vocab_size=VOCAB, d_model=16, n_heads=2,
                       n_layers=1, max_len=MAXLEN)
    dp = dm.init(
        {"params": jax.random.PRNGKey(seed),
         "dropout": jax.random.PRNGKey(seed + 1)},
        jnp.zeros((1, 8), jnp.int32),
    )
    if zero:
        dp = jax.tree_util.tree_map(jnp.zeros_like, dp)
    return DecodeEngine(dm, dp, capacity=eng.capacity,
                        page_size=eng.page_size,
                        pages_per_slot=eng.pages_per_slot,
                        num_pages=eng.cache.num_pages)


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                          n_layers=LAYERS, max_len=MAXLEN)
    params = model.init(
        {"params": jax.random.PRNGKey(0),
         "dropout": jax.random.PRNGKey(1)},
        jnp.zeros((1, 16), jnp.int32),
    )
    return model, params


@pytest.fixture(scope="module")
def lm_long():
    """A longer-context twin of ``lm`` for the int8 handoff gate: the
    greedy-token-divergence test needs >= 64 generated tokens, which
    MAXLEN=64 cannot hold on top of a prompt."""
    model = TransformerLM(vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                          n_layers=LAYERS, max_len=96)
    params = model.init(
        {"params": jax.random.PRNGKey(0),
         "dropout": jax.random.PRNGKey(1)},
        jnp.zeros((1, 16), jnp.int32),
    )
    return model, params


def _prompts(seed, n, lo=2, hi=14):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, rng.randint(lo, hi)).tolist()
            for _ in range(n)]


# ----------------------------------------------------------------------
# allocator
# ----------------------------------------------------------------------
class TestAllocator:
    def _cache(self, capacity=3, num_pages=10, page_size=4):
        return PagedKVCache(
            n_layers=1, n_heads=2, d_head=4, capacity=capacity,
            page_size=page_size, num_pages=num_pages, pages_per_slot=4,
        )

    def test_admit_reserves_ceil_pages(self):
        c = self._cache()
        s = c.admit(9)  # ceil(9/4) = 3 pages
        assert len(c._slot_pages[s]) == 3
        assert c.free_pages == 9 - 3
        c.check_invariants()

    def test_null_page_never_allocated(self):
        c = self._cache()
        slots = [c.admit(16) for _ in range(2)]
        for s in slots:
            assert NULL_PAGE not in c._slot_pages[s]
        c.check_invariants()

    def test_admit_is_deterministic(self):
        def run():
            c = self._cache()
            ops = []
            s0 = c.admit(7); ops.append(("a", s0))
            s1 = c.admit(4); ops.append(("a", s1))
            c.release(s0); ops.append(("r", s0))
            s2 = c.admit(12); ops.append(("a", s2))
            return ops, c.block_tables.copy(), list(c._free_pages)

        a, ta, fa = run()
        b, tb, fb = run()
        assert a == b
        np.testing.assert_array_equal(ta, tb)
        assert fa == fb

    def test_no_fragmentation(self):
        """Pages are unit-granularity: after any release pattern, a
        request fits iff the free COUNT suffices — there is no layout
        in which can_admit lies."""
        c = self._cache(capacity=4, num_pages=9, page_size=4)
        slots = [c.admit(8) for _ in range(4)]  # 2 pages each = all 8
        assert not c.can_admit(4)
        c.release(slots[0])
        c.release(slots[2])  # free pages now interleaved with used
        assert c.can_admit(16)  # 4 pages — would span the "holes"
        s = c.admit(16)
        assert len(c._slot_pages[s]) == 4
        c.check_invariants()

    def test_admission_failures_are_loud(self):
        c = self._cache(capacity=1, num_pages=4, page_size=4)
        assert not c.can_admit(100)  # > pages_per_slot
        with pytest.raises(CacheAdmissionError):
            c.admit(100)
        c.admit(4)
        assert not c.can_admit(4)  # no free slot
        with pytest.raises(CacheAdmissionError):
            c.admit(4)

    def test_eviction_victim_is_latest_admitted(self):
        c = self._cache()
        s0 = c.admit(4)
        s1 = c.admit(4)
        assert c.choose_victim() == s1
        c.evict(s1)
        assert c.choose_victim() == s0
        c.check_invariants()

    def test_advance_past_reservation_raises(self):
        c = self._cache()
        s = c.admit(4)  # one page
        c.advance(s, 4)
        with pytest.raises(CacheAdmissionError):
            c.advance(s, 1)

    def test_release_returns_pages_sorted(self):
        c = self._cache()
        s0, s1 = c.admit(8), c.admit(8)
        c.release(s0)
        assert c._free_pages == sorted(c._free_pages)
        c.release(s1)
        assert c.free_pages == c.num_pages - 1
        c.check_invariants()

    def test_op_mix_invariants(self):
        rng = np.random.RandomState(7)
        c = self._cache(capacity=4, num_pages=12, page_size=4)
        live = []
        for _ in range(200):
            if live and rng.rand() < 0.4:
                c.release(live.pop(rng.randint(len(live))))
            else:
                want = int(rng.randint(1, 16))
                if c.can_admit(want):
                    live.append(c.admit(want))
            c.check_invariants()

    def test_pages_needed(self):
        assert pages_needed(1, 4) == 1
        assert pages_needed(4, 4) == 1
        assert pages_needed(5, 4) == 2
        assert pages_needed(0, 4) == 1  # floor: a slot owns >= 1 page


# ----------------------------------------------------------------------
# cache state round-trip + resharding
# ----------------------------------------------------------------------
class TestCacheState:
    def _populated(self):
        c = PagedKVCache(n_layers=2, n_heads=2, d_head=4, capacity=3,
                         page_size=4, pages_per_slot=4)
        rng = np.random.RandomState(0)
        c.k_pages = jnp.asarray(rng.randn(*c.k_pages.shape), c.dtype)
        c.v_pages = jnp.asarray(rng.randn(*c.v_pages.shape), c.dtype)
        s0 = c.admit(10)
        c.admit(5)
        c.advance(s0, 7)
        return c

    def test_state_dict_round_trip_bit_identical(self):
        c = self._populated()
        state = c.state_dict()
        c2 = PagedKVCache(n_layers=2, n_heads=2, d_head=4, capacity=3,
                          page_size=4, pages_per_slot=4)
        c2.load_state_dict(state)
        np.testing.assert_array_equal(
            np.asarray(c.k_pages), np.asarray(c2.k_pages))
        np.testing.assert_array_equal(c.block_tables, c2.block_tables)
        np.testing.assert_array_equal(c.lengths, c2.lengths)
        assert c._free_pages == c2._free_pages
        assert c._slot_pages == c2._slot_pages
        # the restored allocator continues identically
        assert c.can_admit(20) == c2.can_admit(20)
        assert c.admit(6) == c2.admit(6)
        np.testing.assert_array_equal(c.block_tables, c2.block_tables)

    def test_step_inputs_are_copies_of_the_host_bookkeeping(self):
        """A step is dispatched asynchronously and the host goes on to
        ``advance`` / ``release`` in place; the CPU backend takes an
        aligned host buffer without copying it.  What the step reads
        must therefore not alias ``lengths`` / ``block_tables`` (it did:
        decode steps read lengths one ahead, whenever numpy happened to
        allocate them 64-byte aligned)."""
        c = self._populated()
        c.lengths = _aligned_copy(c.lengths)
        c.block_tables = _aligned_copy(c.block_tables)
        lengths, tables = c.lengths_array(), c.tables_array()
        want = c.lengths.copy(), c.block_tables.copy()
        c.advance(0, 1)
        c.release(1)
        assert not np.array_equal(c.lengths, want[0])
        assert not np.array_equal(c.block_tables, want[1])
        np.testing.assert_array_equal(np.asarray(lengths), want[0])
        np.testing.assert_array_equal(np.asarray(tables), want[1])

    def test_shape_mismatch_rejected(self):
        c = self._populated()
        state = c.state_dict()
        small = PagedKVCache(n_layers=1, n_heads=2, d_head=4,
                             capacity=3, page_size=4, pages_per_slot=4)
        with pytest.raises(ValueError, match="shape mismatch"):
            small.load_state_dict(state)

    def test_dense_oracle_cache_state_round_trips(self, lm):
        """The shape check validates against the CURRENT pool arrays —
        the dense-layout engine replaces them with its contiguous
        per-slot layout, and its own snapshot must round-trip too
        (review regression: the check was hardcoded to the paged
        geometry, so a dense engine rejected its own state_dict)."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           layout="dense")
        slot = eng.admit(8)
        eng.prefill(slot, [1, 2, 3])
        state = eng.cache.state_dict()
        eng2 = DecodeEngine(model, params, capacity=2, page_size=8,
                            layout="dense")
        eng2.cache.load_state_dict(state)
        np.testing.assert_array_equal(
            np.asarray(eng.cache.k_pages), np.asarray(eng2.cache.k_pages))
        np.testing.assert_array_equal(
            eng.cache.lengths, eng2.cache.lengths)

    def test_checkpoint_layer_round_trip(self, tmp_path):
        """The acceptance satellite: cache state rides the EXISTING
        checkpoint layer (save -> resume -> load) bit-identically —
        the replica warm-start path."""
        comm = cmn.create_communicator("single_node")
        ckpt = cmn.create_multi_node_checkpointer(
            "serve", comm, path=str(tmp_path))
        c = self._populated()
        ckpt.save(1, {"kv_cache": c.state_dict()})
        ckpt.wait_until_finished()
        step, restored = ckpt.resume()
        assert step == 1
        c2 = PagedKVCache(n_layers=2, n_heads=2, d_head=4, capacity=3,
                          page_size=4, pages_per_slot=4)
        c2.load_state_dict(restored["kv_cache"])
        np.testing.assert_array_equal(
            np.asarray(c.k_pages), np.asarray(c2.k_pages))
        np.testing.assert_array_equal(
            np.asarray(c.v_pages), np.asarray(c2.v_pages))
        np.testing.assert_array_equal(c.block_tables, c2.block_tables)
        assert c._slot_pages == c2._slot_pages

    def test_reshard_heads_bit_identical_to_fresh_split(self):
        """N->M TP resharding of the page pool == a fresh split of the
        concatenated global cache (heads axis), any N->M."""
        rng = np.random.RandomState(1)
        full_k = rng.randn(2, 5, 4, 8, 4).astype(np.float32)
        full_v = rng.randn(2, 5, 4, 8, 4).astype(np.float32)

        def split(arr, n):
            return [arr[:, :, :, r * 8 // n:(r + 1) * 8 // n]
                    for r in range(n)]

        base = {"block_tables": np.zeros((2, 2), np.int32),
                "lengths": np.zeros((2,), np.int32),
                "active": np.zeros((2,), np.int8),
                "slot_page_counts": np.zeros((2,), np.int32),
                "admit_order": np.zeros((0,), np.int32)}
        for old, new in [(2, 4), (4, 2), (2, 1), (1, 4), (4, 4)]:
            states = [
                dict(base, k_pages=k, v_pages=v)
                for k, v in zip(split(full_k, old), split(full_v, old))
            ]
            out = reshard_kv_state(states, new)
            want_k = split(full_k, new)
            assert len(out) == new
            for got, want in zip(out, want_k):
                np.testing.assert_array_equal(
                    np.asarray(got["k_pages"]), want)

    def test_reshard_rejects_indivisible_heads(self):
        states = [{"k_pages": np.zeros((1, 2, 2, 3, 2)),
                   "v_pages": np.zeros((1, 2, 2, 3, 2))}]
        with pytest.raises(ValueError, match="heads"):
            reshard_kv_state(states, 2)


# ----------------------------------------------------------------------
# KV delta snapshots (ISSUE 19): dirty-page increments for the RAM tier
# ----------------------------------------------------------------------
class TestKVDeltaSnapshot:
    def _replica_pair(self):
        """A populated cache and a replica synced by one full snapshot,
        with agreed delta base markers — the handoff every delta ships
        on top of."""
        c = _cache()
        rng = np.random.RandomState(3)
        c.k_pages = jnp.asarray(rng.randn(*c.k_pages.shape), c.dtype)
        c.v_pages = jnp.asarray(rng.randn(*c.v_pages.shape), c.dtype)
        s0 = c.admit(9)
        c.advance(s0, 8)
        r = _cache()
        r.load_state_dict(c.state_dict())
        r.delta_base_mark(c.delta_base_mark())
        return c, r, s0

    def _assert_synced(self, c, r):
        a, b = c.state_dict(), r.state_dict()
        assert sorted(a) == sorted(b)
        for k in a:
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]), err_msg=k
            )

    def test_delta_ships_only_the_dirty_pages(self):
        c, r, _ = self._replica_pair()
        s1 = c.admit(5)
        c.advance(s1, 3)
        touched = set(c._slot_pages[s1])
        d = c.delta_state_dict()
        assert {int(p) for p in d["page_ids"]} == touched
        assert d["k_delta"].shape[1] == len(touched)
        r.apply_delta(d)
        self._assert_synced(c, r)
        # nothing written since the cut: the next delta is empty but
        # still carries the full accounting and verifies
        d2 = c.delta_state_dict()
        assert d2["page_ids"].size == 0
        r.apply_delta(d2)
        self._assert_synced(c, r)

    def test_admit_cow_evict_release_churn_applies_bit_identical(self):
        """The acceptance pin: a delta cut after prefix-shared
        admission, a copy-on-write, an eviction, and a release lands
        the replica bit-identical to loading the sender's FULL
        state_dict — refcounts and CoW reserves included."""
        c, r, s0 = self._replica_pair()
        toks = list(range(8))
        c.register_prefix(s0, toks)
        m = c.lookup_prefix(toks)  # full match → CoW'd final page
        b = c.admit(12, prefix=m)
        assert c.cow_for_write(b, 1) is True
        c.advance(b, 1)
        u = c.admit(5)
        c.advance(u, 5)
        c.evict(u)  # preempt: pages return to the pool
        c.release(s0)  # shared pages survive for b alone
        r.apply_delta(c.delta_state_dict())
        self._assert_synced(c, r)
        # the synced replica's allocator continues identically
        assert c.admit(6) == r.admit(6)
        np.testing.assert_array_equal(c.block_tables, r.block_tables)
        assert c._free_pages == r._free_pages
        c.check_invariants()
        r.check_invariants()

    def test_import_kv_marks_the_imported_pages_dirty(self):
        # the disaggregated handoff writes pages outside admit/advance:
        # those must land in the next delta too
        c, _, s0 = self._replica_pair()
        kv = c.export_kv(s0)
        dst = _cache()
        dst.delta_base_mark()
        slot = dst.import_kv(kv, 12)
        cut = dst.delta_state_dict()
        assert {int(p) for p in cut["page_ids"]} == set(
            dst._slot_pages[slot]
        )

    def test_tampered_delta_rejected_before_any_mutation(self):
        c, r, _ = self._replica_pair()
        s1 = c.admit(5)
        c.advance(s1, 3)
        d = c.delta_state_dict()
        before = r.state_dict()
        evil = dict(d, k_delta=np.asarray(d["k_delta"]) + 1e-3)
        with pytest.raises(ValueError, match="digest mismatch"):
            r.apply_delta(evil)
        # accounting is covered by the digest as well
        evil2 = dict(d, lengths=np.asarray(d["lengths"]) + 1)
        with pytest.raises(ValueError, match="digest mismatch"):
            r.apply_delta(evil2)
        after = r.state_dict()
        for k in before:
            np.testing.assert_array_equal(
                np.asarray(before[k]), np.asarray(after[k]), err_msg=k
            )
        r.apply_delta(d)  # the pristine delta still applies
        self._assert_synced(c, r)

    def test_out_of_order_delta_rejected(self):
        c, r, _ = self._replica_pair()
        s1 = c.admit(5)
        c.advance(s1, 2)
        d1 = c.delta_state_dict()
        c.advance(s1, 1)
        d2 = c.delta_state_dict()
        with pytest.raises(ValueError, match="base marker"):
            r.apply_delta(d2)  # skipped d1
        r.apply_delta(d1)
        r.apply_delta(d2)  # in order: lands
        self._assert_synced(c, r)
        with pytest.raises(ValueError, match="base marker"):
            r.apply_delta(d2)  # replay


# ----------------------------------------------------------------------
# flash_decode kernel (decode-geometry Pallas variant)
# ----------------------------------------------------------------------
class TestFlashDecode:
    def _pages(self, seed=0, B=3, H=4, Dh=32, bs=8, P=12, n=3):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(B, H, Dh), jnp.float32)
        k = jnp.asarray(rng.randn(P, bs, H, Dh), jnp.float32)
        v = jnp.asarray(rng.randn(P, bs, H, Dh), jnp.float32)
        bt = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
        return q, k, v, bt

    def test_matches_dense_reference_ragged(self):
        q, k, v, bt = self._pages()
        lengths = jnp.asarray([20, 9, 3], jnp.int32)  # ragged tails
        out = flash_decode(q, k, v, bt, lengths, interpret=True)
        ref = paged_decode_reference(q, k, v, bt, lengths)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-6, atol=2e-6)

    def test_single_page_matches_dense_reference(self):
        """One live page = online softmax IS the dense softmax, in
        exact arithmetic.  The kernel and the gather reference are two
        programs (the exp, the sums and the divide are not scheduled
        alike), so float32 rounding is the bound, not bit identity."""
        q, k, v, _ = self._pages()
        bt = jnp.asarray([[1], [4], [6]], jnp.int32)
        lengths = jnp.asarray([5, 8, 3], jnp.int32)
        out = flash_decode(q, k, v, bt, lengths, interpret=True)
        ref = paged_decode_reference(q, k, v, bt, lengths)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-6, atol=2e-6)

    def test_zero_length_slot_returns_zeros(self):
        q, k, v, bt = self._pages()
        lengths = jnp.asarray([20, 0, 3], jnp.int32)
        out = flash_decode(q, k, v, bt, lengths, interpret=True)
        assert np.all(np.asarray(out)[1] == 0)
        ref = paged_decode_reference(q, k, v, bt, lengths)
        assert np.all(np.asarray(ref)[1] == 0)

    def test_dead_pages_do_not_contribute(self):
        """Pages past length are skipped entirely: poisoning them (with
        huge finite values) must not change the output."""
        q, k, v, bt = self._pages()
        lengths = jnp.asarray([9, 9, 3], jnp.int32)  # pages 2.. dead
        out = flash_decode(q, k, v, bt, lengths, interpret=True)
        k2 = k.at[3].set(1e9)  # slot 0's 3rd page — dead at length 9
        v2 = v.at[3].set(1e9)
        out2 = flash_decode(q, k2, v2, bt, lengths, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ----------------------------------------------------------------------
# decode step: paged vs dense-cache oracle, generate parity
# ----------------------------------------------------------------------
class TestDecodeBitExactness:
    def _script(self, layout, lm):
        """A scripted interleave of joins/leaves with ragged lengths;
        returns every logits row produced, in order."""
        model, params = lm
        rng = np.random.RandomState(1)
        p0 = rng.randint(0, VOCAB, 5).tolist()
        p1 = rng.randint(0, VOCAB, 11).tolist()   # ragged vs page 8
        p2 = rng.randint(0, VOCAB, 3).tolist()
        eng = DecodeEngine(model, params, capacity=3, page_size=8,
                           layout=layout)
        logs = []
        s0 = eng.admit(5 + 12)
        l = eng.prefill(s0, p0); logs.append(l); t0 = int(np.argmax(l))
        for _ in range(2):
            tk = np.zeros(3, np.int32); tk[s0] = t0
            lg = eng.decode_step(tk)
            logs.append(lg[s0].copy()); t0 = int(np.argmax(lg[s0]))
        s1 = eng.admit(11 + 6)
        l = eng.prefill(s1, p1); logs.append(l); t1 = int(np.argmax(l))
        for _ in range(3):
            tk = np.zeros(3, np.int32); tk[s0] = t0; tk[s1] = t1
            lg = eng.decode_step(tk)
            logs.append(lg[[s0, s1]].copy())
            t0, t1 = int(np.argmax(lg[s0])), int(np.argmax(lg[s1]))
        eng.release(s0)  # leave mid-stream; s2 joins into freed pages
        s2 = eng.admit(3 + 4)
        l = eng.prefill(s2, p2); logs.append(l); t2 = int(np.argmax(l))
        for _ in range(2):
            tk = np.zeros(3, np.int32); tk[s1] = t1; tk[s2] = t2
            lg = eng.decode_step(tk)
            logs.append(lg[[s1, s2]].copy())
            t1, t2 = int(np.argmax(lg[s1])), int(np.argmax(lg[s2]))
        return logs

    def test_paged_equals_dense_oracle_bit_identical(self, lm):
        """THE acceptance pin: every logits row of the interleaved
        paged run equals the dense contiguous-cache oracle's at 0
        tolerance — joins, leaves, slot reuse, ragged final blocks."""
        paged = self._script("paged", lm)
        dense = self._script("dense", lm)
        assert len(paged) == len(dense)
        for i, (a, b) in enumerate(zip(paged, dense)):
            np.testing.assert_array_equal(a, b, err_msg=f"row {i}")

    def test_generate_parity_with_transformer_tier(self, lm):
        """Greedy serving decode == transformer.generate's KV-cache
        tier, token for token (trained-checkpoint contract)."""
        model, params = lm
        prompt = [3, 9, 4, 1, 5, 60, 2]
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        got = eng.generate(prompt, 10)
        ref = generate(model, params,
                       jnp.asarray([prompt], jnp.int32), 10)
        assert got == np.asarray(ref)[0].tolist()

    def test_flash_impl_matches_dense_impl(self):
        """The Pallas decode fast path agrees with the dense attend
        (fp32 model so the only delta is the kernel's fp32-vs-compute
        dtype flow and online-softmax association)."""
        model = TransformerLM(vocab_size=VOCAB, d_model=D,
                              n_heads=HEADS, n_layers=LAYERS,
                              max_len=MAXLEN, dtype=jnp.float32)
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            jnp.zeros((1, 16), jnp.int32),
        )
        prompt = [7, 1, 42, 9, 3]
        dense = DecodeEngine(model, params, capacity=2, page_size=8)
        flash = DecodeEngine(model, params, capacity=2, page_size=8,
                             attention_impl="flash")
        s_d = dense.admit(5 + 6); s_f = flash.admit(5 + 6)
        ld = dense.prefill(s_d, prompt)
        lf = flash.prefill(s_f, prompt)  # prefill is dense in both
        np.testing.assert_array_equal(ld, lf)
        t = int(np.argmax(ld))
        for _ in range(4):
            tk = np.zeros(2, np.int32); tk[0] = t
            a = dense.decode_step(tk)[0]
            b = flash.decode_step(tk)[0]
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
            t = int(np.argmax(a))

    def test_engine_rejects_training_only_shardings(self, lm):
        model, params = lm
        import dataclasses

        sp = dataclasses.replace(model, seq_axis="mn_seq")
        with pytest.raises(ValueError, match="seq_axis=None"):
            DecodeEngine(sp, params)
        eng = engine_from_trained(sp, params, capacity=2, page_size=8)
        assert eng.module.tp_axis is None  # dense twin materialized

    def test_request_over_capacity_rejected(self, lm):
        model, params = lm
        eng = DecodeEngine(model, params, capacity=1, page_size=8,
                           pages_per_slot=2)
        with pytest.raises(ValueError, match="max_total"):
            eng.admit(17)


# ----------------------------------------------------------------------
# tensor-parallel decode: budget pin + shardlint attribution
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def tp_setup(devices8):
    from jax.sharding import PartitionSpec as P
    from chainermn_tpu.parallel import megatron_param_specs, sharded_init

    comm = cmn.create_communicator("mesh", devices=devices8,
                                   sp_size=1, tp_size=2)
    model = TransformerLM(vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                          n_layers=LAYERS, max_len=MAXLEN,
                          tp_axis="mn_model")
    toks = jnp.zeros((4, 16), jnp.int32)
    params, specs = sharded_init(
        lambda t: model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)}, t),
        comm.mesh, (P("mn_data", "mn_seq"),),
        lambda tree: megatron_param_specs(tree, model_axis="mn_model"),
        toks,
    )
    return comm, model, params, specs


class TestTensorParallelDecode:
    def test_decode_step_budget_pin(self, tp_setup):
        """The decode_step ceiling (2 row-parallel psums per layer,
        nothing else) holds EXACTLY on the authored trace of both the
        decode and the prefill program."""
        from chainermn_tpu.analysis import enforce

        comm, model, params, specs = tp_setup
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           comm=comm, param_specs=specs)
        tr = eng.collective_trace("decode")
        census = enforce("decode_step", tr)
        assert census.get("all_reduce") == 2 * LAYERS  # exact, not just <=
        tr_p = eng.collective_trace("prefill", bucket=8)
        assert enforce("decode_step", tr_p).get("all_reduce") == 2 * LAYERS

    def test_prefill_step_budget_pin(self, tp_setup):
        """ISSUE 18: the prefill program gets its OWN pinned name — a
        disaggregated prefill pool runs nothing else all day, so its
        ceiling must not ride along as a decode_step footnote.  Same
        exact 2-row-parallel-psums-per-layer family, zero partitioner
        insertions on the compiled program."""
        from chainermn_tpu.analysis import assert_attributed, enforce

        comm, model, params, specs = tp_setup
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           comm=comm, param_specs=specs)
        tr = eng.collective_trace("prefill", bucket=8)
        census = enforce("prefill_step", tr)
        assert census.get("all_reduce") == 2 * LAYERS  # exact
        rep = assert_attributed(tr, eng.compiled_text("prefill", bucket=8),
                                name="prefill_step")
        assert rep["all_reduce"]["implicit"] == []
        assert rep["all_reduce"]["authored"] == 2 * LAYERS

    def test_decode_step_attributes_with_zero_insertions(self, tp_setup):
        """Shardlint acceptance: every collective in the COMPILED
        decode step is an authored record — the partitioner inserted
        nothing."""
        from chainermn_tpu.analysis import assert_attributed

        comm, model, params, specs = tp_setup
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           comm=comm, param_specs=specs)
        tr = eng.collective_trace("decode")
        rep = assert_attributed(tr, eng.compiled_text("decode"),
                                name="decode_step")
        assert rep["all_reduce"]["implicit"] == []
        assert rep["all_reduce"]["authored"] == 2 * LAYERS
        assert rep["all_reduce"]["lowered"] == 2 * LAYERS

    def test_tp_generate_parity(self, tp_setup):
        """TP paged decode == the transformer TP generate tier."""
        comm, model, params, specs = tp_setup
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           comm=comm, param_specs=specs)
        prompt = [3, 9, 4, 1, 5]
        got = eng.generate(prompt, 8)
        ref = generate(model, params,
                       jnp.asarray([prompt], jnp.int32), 8,
                       comm=comm, param_specs=specs)
        assert got == np.asarray(ref)[0].tolist()

    def test_tp_requires_comm_and_specs(self, tp_setup):
        _comm, model, params, _specs = tp_setup
        with pytest.raises(ValueError, match="mesh"):
            DecodeEngine(model, params, capacity=2)


# ----------------------------------------------------------------------
# continuous batching
# ----------------------------------------------------------------------
class TestContinuousBatcher:
    def test_batched_outputs_equal_single_request_outputs(self, lm):
        """Continuous batching is a SCHEDULING optimization: every
        request's tokens are an unbatched run's (a capacity-1 engine is
        another program: the same greedy decode, ``_assert_same_greedy``)."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=3, page_size=8)
        reqs = [Request(p, 2 + (i % 5))
                for i, p in enumerate(_prompts(11, 7))]
        out = ContinuousBatcher(eng).serve(reqs)
        solo = DecodeEngine(model, params, capacity=1, page_size=8)
        for r in out:
            assert r.state == "done", r
            _assert_same_greedy(
                lm, r.output, solo.generate(r.prompt, r.max_new_tokens))

    def test_joins_and_leaves_share_compiled_programs(self, lm):
        """Padded slot model: membership churn across the whole serve
        never retraces — one decode program per capacity, one prefill
        per prompt bucket."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        b = ContinuousBatcher(eng)
        b.serve([Request(p, 3) for p in _prompts(5, 6, lo=2, hi=16)])
        sizes = getattr(eng._fn, "_cache_size", None)
        if callable(sizes):
            buckets = {eng.prompt_bucket(len(p))
                       for p in _prompts(5, 6, lo=2, hi=16)}
            assert eng._fn._cache_size() <= 1 + len(buckets)

    def test_eos_retires_early(self, lm):
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        probe = eng.generate([5, 9, 11], 6)
        eos, n = _first_fresh_token(probe[3:])
        r = Request([5, 9, 11], 6, eos_id=eos)
        out = ContinuousBatcher(eng).serve([r])[0]
        assert out.state == "done"
        assert out.tokens == probe[3:3 + n]
        assert 1 < n < 6  # stopped early, and not on the first token

    def test_recoverable_fault_retries_and_outputs_match(self, lm):
        """An injected transient at the decode step re-queues the
        in-flight requests; the retried outputs are the unbatched
        run's greedy decode (the request-level slice of the resilience
        taxonomy)."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        reqs = [Request(p, 4) for p in _prompts(21, 3)]
        from chainermn_tpu.resilience.log import ResilienceLog, attach, detach

        slog = ResilienceLog()
        attach(slog)
        try:
            with inject_faults(
                [FaultSpec("serving.decode_step", "timeout", at=[2])]
            ):
                out = ContinuousBatcher(eng, max_retries=2).serve(reqs)
        finally:
            detach(slog)
        assert slog.counts.get("request_retry", 0) >= 1
        solo = DecodeEngine(model, params, capacity=1, page_size=8)
        for r in out:
            assert r.state == "done"
            assert r.retries >= 0
            _assert_same_greedy(
                lm, r.output, solo.generate(r.prompt, r.max_new_tokens))

    def test_retry_budget_exhaustion_fails_request_not_batch(self, lm):
        model, params = lm
        eng = DecodeEngine(model, params, capacity=1, page_size=8)
        reqs = [Request(p, 3) for p in _prompts(31, 2)]
        # every decode step of the FIRST request faults; with
        # max_retries=0 it fails, and the second request (served after)
        # completes untouched by the exhausted spec
        with inject_faults(
            [FaultSpec("serving.decode_step", "timeout", at=[1],
                       max_fires=1)]
        ):
            out = ContinuousBatcher(eng, max_retries=0).serve(reqs)
        states = sorted(r.state for r in out)
        assert states == ["done", "failed"]
        failed = [r for r in out if r.state == "failed"][0]
        assert "retries exhausted" in failed.error

    def test_timeout_fails_overdue_requests(self, lm):
        model, params = lm
        eng = DecodeEngine(model, params, capacity=1, page_size=8)
        b = ContinuousBatcher(eng, timeout_s=0.0)
        r0 = b.submit(Request(_prompts(41, 1)[0], 3))
        import time as _t

        _t.sleep(0.01)
        b.run()
        assert r0.state == "failed" and "timeout" in r0.error

    def test_request_larger_than_pool_rejected_at_submit(self, lm):
        """A request that outsizes the ALLOCATABLE pool (explicit small
        num_pages) can never be admitted: submit() must reject it up
        front — queueing it would spin the serving loop forever with
        zero progress (review regression: only the slot-width bound
        was checked)."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           num_pages=3, pages_per_slot=4)
        assert eng.max_total == 16  # 2 allocatable pages * 8
        b = ContinuousBatcher(eng)
        with pytest.raises(ValueError, match="max_total"):
            b.submit(Request(list(range(20)), 8))

    def test_timeout_rejected_in_multiprocess_world(self):
        """timeout_s reads the rank-LOCAL monotonic clock: two ranks
        straddling the deadline would diverge their admission
        schedules and deadlock the decode psums — a multi-process TP
        world must reject it at construction."""

        class _Comm:
            process_count = 2

        class _Engine:
            comm = _Comm()

        with pytest.raises(ValueError, match="timeout_s"):
            ContinuousBatcher(_Engine(), timeout_s=1.0)

    def test_latency_report_and_spans(self, lm):
        from chainermn_tpu import observability as obs

        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        tel = obs.Telemetry(label="serve-test")
        obs.install(tel)
        try:
            b = ContinuousBatcher(eng)
            b.serve([Request(p, 3) for p in _prompts(51, 3)])
        finally:
            obs.install(None)
        rep = b.latency_report()
        assert rep["done"] == 3 and rep["failed"] == 0
        assert rep["tokens_generated"] == 9
        assert "serving.token_latency" in rep
        assert rep["serving.token_latency"]["n"] > 0
        assert rep["serving.ttft"]["n"] == 3
        names = {s["name"] for s in tel.timeline.spans()}
        assert {"serving.step", "serving.prefill",
                "serving.decode"} <= names

    def test_attribution_joins_decode_trace(self, tp_setup):
        """The latency-attribution hook: attribute() over a serving
        timeline + the engine's decode trace returns the full record
        list (never drops) — the docs/serving.md recipe."""
        from chainermn_tpu import observability as obs

        comm, model, params, specs = tp_setup
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           comm=comm, param_specs=specs)
        tel = obs.Telemetry(label="attr-test")
        obs.install(tel)
        try:
            ContinuousBatcher(eng).serve(
                [Request([1, 2, 3], 2)]
            )
        finally:
            obs.install(None)
        rep = eng.attribution(tel.timeline)
        # compiled-step collectives have no per-collective spans on
        # this path — the report must LIST them as unmatched rather
        # than drop them (attribute()'s never-drop contract)
        total = len(rep.matched) + len(rep.unmatched_records)
        assert total == 2 * LAYERS


# ----------------------------------------------------------------------
# elastic replicas
# ----------------------------------------------------------------------
class TestReplica:
    def test_claim_is_disjoint_complete_and_stable(self):
        docs = [{"id": f"r{i}", "seq": i} for i in range(7)]
        a = claim(docs, 0, 2)
        b = claim(docs, 1, 2)
        assert {d["id"] for d in a} | {d["id"] for d in b} == {
            f"r{i}" for i in range(7)}
        assert not ({d["id"] for d in a} & {d["id"] for d in b})
        # stability: removing served requests does not migrate the rest
        remaining = [d for d in docs if d["id"] not in ("r0", "r2")]
        a2 = claim(remaining, 0, 2)
        assert {d["id"] for d in a2} == {"r4", "r6"}

    def test_journal_seq_ignores_torn_tmp_files(self, tmp_path):
        """seq derives from the COMMITTED request files (max + 1), so
        a crashed submitter's leftover ``.tmp`` can neither skip seqs
        nor shadow one (review regression: counting every ``req_``
        prefix included tmp files)."""
        j = RequestJournal(str(tmp_path))
        j.submit(Request([1], 2, id="a"))
        open(os.path.join(str(tmp_path),
                          "req_000001_ghost.json.tmp999"), "w").close()
        j.submit(Request([2], 2, id="b"))
        assert [(d["id"], d["seq"]) for d in j.requests()] == [
            ("a", 0), ("b", 1)]

    def test_journal_round_trip(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        reqs = [Request([1, 2, 3], 4, id=f"r{i}") for i in range(3)]
        j.submit_all(reqs)
        assert [d["id"] for d in j.requests()] == ["r0", "r1", "r2"]
        assert len(j.pending()) == 3
        reqs[1].tokens = [7, 8]
        reqs[1].state = "done"
        j.write_result(reqs[1])
        assert [d["id"] for d in j.pending()] == ["r0", "r2"]
        assert j.results()["r1"]["tokens"] == [1, 2, 3, 7, 8]

    def test_unservable_journaled_request_fails_loudly(self, lm,
                                                       tmp_path):
        """A journaled request NO engine of this replica's geometry can
        admit must fail in the journal (loud, result written) while the
        rest of the share completes — crashing or wedging the claim
        loop would take every other request down with it."""
        model, params = lm
        j = RequestJournal(str(tmp_path))
        j.submit_all([Request(list(range(20)), 8, id="big"),
                      Request([1, 2, 3], 3, id="ok")])
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           num_pages=3, pages_per_slot=4)
        rep = DecodeReplica(eng, j)
        rep.serve()
        res = j.results()
        assert res["big"]["state"] == "failed"
        assert "max_total" in res["big"]["error"]
        assert res["ok"]["state"] == "done"
        assert len(j.pending()) == 0

    def test_two_replicas_partition_stream(self, lm, tmp_path):
        model, params = lm
        j = RequestJournal(str(tmp_path))
        j.submit_all([Request(p, 3, id=f"r{i}")
                      for i, p in enumerate(_prompts(61, 5))])
        reps = [
            DecodeReplica(
                DecodeEngine(model, params, capacity=2, page_size=8),
                j, replica_index=i, n_replicas=2)
            for i in range(2)
        ]
        s0 = reps[0].serve()
        s1 = reps[1].serve()
        assert sorted(s0) == ["r0", "r2", "r4"]
        assert sorted(s1) == ["r1", "r3"]
        assert len(j.pending()) == 0

    def test_preempt_drains_and_survivor_completes_bit_identical(
            self, lm, tmp_path):
        """The elastic-replica acceptance, single-process tier (the mp
        tier's serving_churn scenario runs it across real processes
        with a hard kill): a preemption notice drains the replica
        mid-stream — queued requests stay journaled — and the
        re-formed world completes them with outputs bit-identical to
        the no-fault run."""
        model, params = lm
        j = RequestJournal(str(tmp_path))
        docs = [Request(p, 3, id=f"q{i}")
                for i, p in enumerate(_prompts(71, 4))]
        j.submit_all(docs)
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        rep = DecodeReplica(eng, j, replica_index=0, n_replicas=1)
        with inject_faults(
            [FaultSpec("serving.decode_step", "preempt", at=[2])]
        ):
            rep.serve()
        assert rep.drained
        assert len(j.pending()) == 4  # nothing dropped
        # no-fault oracle
        oracle_eng = DecodeEngine(model, params, capacity=2, page_size=8)
        oracle = {r.id: oracle_eng.generate(r.prompt, r.max_new_tokens)
                  for r in docs}
        survivor = DecodeReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, replica_index=0, n_replicas=1)
        survivor.serve()
        assert len(j.pending()) == 0
        res = j.results()
        for rid, want in oracle.items():
            assert res[rid]["tokens"] == want, rid

    def test_warm_start_resumes_in_flight_bit_identical(
            self, lm, tmp_path):
        """The warm-start contract end to end: a preempted replica
        with a checkpointer drains pages AND in-flight request state;
        the rejoining replica adopts those requests — resuming decode
        mid-stream from the restored pages instead of replaying the
        prompt — and completes the whole stream bit-identically to the
        no-fault run (review regression: restored-active slots had no
        owning request, wedging admission forever when the drained
        cache was full)."""
        model, params = lm
        comm = cmn.create_communicator("single_node")
        ckpt = cmn.create_multi_node_checkpointer(
            "warm", comm, path=str(tmp_path / "ck"))
        j = RequestJournal(str(tmp_path / "j"))
        docs = [Request(p, 4, id=f"w{i}")
                for i, p in enumerate(_prompts(81, 3))]
        j.submit_all(docs)
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        rep = DecodeReplica(eng, j, checkpointer=ckpt)
        with inject_faults(
            [FaultSpec("serving.decode_step", "preempt", at=[2])]
        ):
            rep.serve()
        assert rep.drained
        ckpt.wait_until_finished()
        oracle_eng = DecodeEngine(model, params, capacity=2, page_size=8)
        oracle = {r.id: oracle_eng.generate(r.prompt, r.max_new_tokens)
                  for r in docs}
        eng2 = DecodeEngine(model, params, capacity=2, page_size=8)
        rep2 = DecodeReplica(eng2, j, checkpointer=ckpt)
        assert rep2.warm_start() is not None
        # the drained in-flight requests were adopted mid-decode:
        # tokens already generated, slots still occupied, and the
        # timeout deadline restarted (submitted_at set — a None would
        # exempt resumed requests from timeout_s forever)
        assert rep2.batcher.active
        assert all(r.tokens for r in rep2.batcher.active.values())
        assert all(r.submitted_at is not None
                   for r in rep2.batcher.active.values())
        rep2.serve()
        assert len(j.pending()) == 0
        res = j.results()
        for rid, want in oracle.items():
            assert res[rid]["tokens"] == want, rid

    def test_drain_snapshot_warm_start(self, lm, tmp_path):
        """drain() routes the cache through the checkpoint layer;
        warm_start() on a fresh replica restores the pages
        bit-identically — and releases a restored-active slot no
        in-flight request owns (the engine-driven admit here never
        registered with the batcher, so nothing would ever free it;
        keeping it would wedge admission forever)."""
        model, params = lm
        comm = cmn.create_communicator("single_node")
        ckpt = cmn.create_multi_node_checkpointer(
            "replica", comm, path=str(tmp_path / "ck"))
        j = RequestJournal(str(tmp_path / "j"))
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        rep = DecodeReplica(eng, j, checkpointer=ckpt)
        slot = eng.admit(8)
        eng.prefill(slot, [1, 2, 3, 4])
        rep.drain(step=1)
        ckpt.wait_until_finished()
        eng2 = DecodeEngine(model, params, capacity=2, page_size=8)
        rep2 = DecodeReplica(eng2, j, checkpointer=ckpt)
        assert rep2.warm_start() == 1
        np.testing.assert_array_equal(
            np.asarray(eng.cache.k_pages), np.asarray(eng2.cache.k_pages))
        # the orphaned slot was released: full capacity is admittable
        # again and the allocator is consistent
        assert not eng2.cache.active[slot]
        assert eng2.cache.free_pages == eng2.cache.num_pages - 1
        eng2.cache.check_invariants()


# ----------------------------------------------------------------------
# adaptive drain (ISSUE 15): the serving escalation of the
# straggler-adaptive policy
# ----------------------------------------------------------------------
class TestAdaptiveDrain:
    """``drain_replica`` marks the slow replica draining in the
    journal; the deterministic ``seq % n`` claim re-derives around it,
    so the draining replica's share migrates to healthy replicas with
    no coordination — and every request still completes bit-identically
    to a fresh oracle engine (the ISSUE 15 serving acceptance)."""

    def test_claim_reassigns_draining_share_disjoint_complete(self):
        docs = [{"id": f"r{i}", "seq": i} for i in range(12)]
        shares = [claim(docs, k, 3, draining=[1]) for k in range(3)]
        ids = [{d["id"] for d in s} for s in shares]
        # the draining replica claims nothing; the others partition the
        # whole stream disjointly
        assert ids[1] == set()
        assert ids[0] | ids[2] == {f"r{i}" for i in range(12)}
        assert not ids[0] & ids[2]
        # deterministic: the reassignment is a pure function of seq and
        # the draining set, so every replica derives the same partition
        again = [claim(docs, k, 3, draining=[1]) for k in range(3)]
        assert [{d["id"] for d in s} for s in again] == ids
        # base shares of healthy replicas are unchanged (only the
        # draining replica's share moved)
        base0 = {d["id"] for d in claim(docs, 0, 3)}
        assert base0 <= ids[0]

    def test_all_draining_falls_back_to_base_partition(self):
        docs = [{"id": f"r{i}", "seq": i} for i in range(6)]
        shares = [claim(docs, k, 2, draining=[0, 1]) for k in range(2)]
        # a fully draining world must keep serving, not wedge
        assert {d["id"] for d in shares[0]} == {"r0", "r2", "r4"}
        assert {d["id"] for d in shares[1]} == {"r1", "r3", "r5"}

    def test_journal_drain_markers_round_trip(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        assert j.draining() == []
        j.mark_draining(2)
        j.mark_draining(0)
        assert j.draining() == [0, 2]
        j.clear_draining(2)
        assert j.draining() == [0]
        # markers never pollute the request/result scans
        j.submit(Request([1], 2, id="a"))
        assert [d["id"] for d in j.requests()] == ["a"]
        assert j.results() == {}

    def test_drained_replica_share_migrates_bit_identical(
        self, lm, tmp_path
    ):
        """The acceptance path: replica 1 is convicted slow and
        drained; replica 0 completes the WHOLE stream — including the
        migrated share — with outputs bit-identical to a fresh
        single-engine oracle, while the drained replica claims nothing
        new."""
        from chainermn_tpu.resilience.adaptive import drain_replica
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        model, params = lm
        j = RequestJournal(str(tmp_path))
        docs = [Request(p, 3, id=f"d{i}")
                for i, p in enumerate(_prompts(91, 6))]
        j.submit_all(docs)
        slog = ResilienceLog()
        attach(slog)
        try:
            drain_replica(j, 1, reason="convicted straggler")
        finally:
            detach(slog)
        dec = slog.events("adapt_decision")
        assert dec and dec[0].info["action"] == "drain"
        assert dec[0].info["process"] == 1
        assert slog.events("adapt_action", "adaptive.drain")
        # the draining replica serves nothing new
        drained = DecodeReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, replica_index=1, n_replicas=2)
        assert drained.serve() == {}
        # the healthy replica absorbs the whole stream
        healthy = DecodeReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, replica_index=0, n_replicas=2)
        healthy.serve()
        assert len(j.pending()) == 0
        oracle_eng = DecodeEngine(model, params, capacity=2,
                                  page_size=8)
        res = j.results()
        for r in docs:
            want = oracle_eng.generate(r.prompt, r.max_new_tokens)
            assert res[r.id]["tokens"] == want, r.id

    def test_cleared_drain_restores_base_claim(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        j.submit_all([Request([1], 1, id=f"c{i}") for i in range(4)])
        j.mark_draining(1)
        assert claim(j.pending(), 1, 2,
                     draining=j.draining()) == []
        j.clear_draining(1)
        share = claim(j.pending(), 1, 2, draining=j.draining())
        assert {d["id"] for d in share} == {"c1", "c3"}


# ----------------------------------------------------------------------
class TestReplicaAutoscaler:
    """ISSUE 16: load-driven replica-pool sizing over the journal's
    drain markers — AdaptPolicy-shaped hysteresis, one decision maker,
    the markers as the broadcast."""

    def _scaler(self, tmp_path, **kw):
        from chainermn_tpu.serving import ReplicaAutoscaler

        j = RequestJournal(str(tmp_path))
        kw.setdefault("scale_after", 2)
        kw.setdefault("cooldown_windows", 1)
        return j, ReplicaAutoscaler(j, 4, **kw)

    def test_validation_is_eager(self, tmp_path):
        from chainermn_tpu.serving import ReplicaAutoscaler

        j = RequestJournal(str(tmp_path))
        with pytest.raises(ValueError, match="pool_size"):
            ReplicaAutoscaler(j, 0)
        with pytest.raises(ValueError, match="min_replicas"):
            ReplicaAutoscaler(j, 2, min_replicas=3)
        with pytest.raises(ValueError, match="scale_after"):
            ReplicaAutoscaler(j, 2, scale_after=0)
        with pytest.raises(ValueError, match="queue_per_replica"):
            ReplicaAutoscaler(j, 2, queue_per_replica=0)

    def test_scale_up_needs_sustained_pressure_then_cools_down(
        self, tmp_path
    ):
        from chainermn_tpu.resilience.log import (
            ResilienceLog, attach, detach,
        )

        j, a = self._scaler(tmp_path, queue_per_replica=4)
        j.mark_draining(2)
        j.mark_draining(3)
        assert a.active() == [0, 1]
        slog = ResilienceLog()
        attach(slog)
        try:
            # 2 active * 4/replica = 8 capacity; 20 queued is pressure
            assert a.observe(queue_depth=20) is None  # streak 1
            act = a.observe(queue_depth=20)
            assert act == {"action": "scale_up", "replica": 2,
                           "active": 3, "queue_depth": 20}
            assert a.active() == [0, 1, 2]  # marker lifted
            # cooldown blocks the immediate next window (the streak
            # keeps accumulating under it — AdaptPolicy's shape)
            assert a.observe(queue_depth=20) is None
            act2 = a.observe(queue_depth=20)
            assert act2["action"] == "scale_up" and act2["replica"] == 3
        finally:
            detach(slog)
        decs = slog.events("autoscale_decision")
        assert [e.info["action"] for e in decs] == ["scale_up"] * 2
        assert slog.events("autoscale_action")
        assert a.totals == {"scale_up": 2, "scale_down": 0}
        # pool exhausted: pressure can no longer accumulate a streak
        assert a.observe(queue_depth=99) is None
        assert a.observe(queue_depth=99) is None
        assert a.streaks == {"up": 0, "down": 0}

    def test_scale_down_sheds_highest_active_to_min(self, tmp_path):
        j, a = self._scaler(tmp_path, queue_per_replica=4,
                            min_replicas=2, cooldown_windows=0)
        assert a.active() == [0, 1, 2, 3]
        # queue 4 <= 4 * (4-1): relief
        assert a.observe(queue_depth=4) is None
        act = a.observe(queue_depth=4)
        assert act == {"action": "scale_down", "replica": 3,
                       "active": 3, "queue_depth": 4}
        assert j.draining() == [3]
        a.observe(queue_depth=0)
        act2 = a.observe(queue_depth=0)
        assert act2["action"] == "scale_down" and act2["replica"] == 2
        # at min_replicas the down streak stops accumulating
        assert a.observe(queue_depth=0) is None
        assert a.observe(queue_depth=0) is None
        assert a.active() == [0, 1]

    def test_flapping_load_never_scales(self, tmp_path):
        j, a = self._scaler(tmp_path, queue_per_replica=4,
                            min_replicas=1)
        j.mark_draining(3)
        # pressure / relief alternating: neither streak survives
        for depth in (99, 0, 99, 0, 99, 0):
            assert a.observe(queue_depth=depth) is None
        assert a.totals == {"scale_up": 0, "scale_down": 0}

    def test_p99_latency_is_a_scale_up_signal(self, tmp_path):
        j, a = self._scaler(tmp_path, queue_per_replica=100,
                            p99_high_s=0.5)
        j.mark_draining(3)
        # queue is shallow but the pool is slow: p99 drives the streak
        assert a.observe(queue_depth=1, p99_token_s=2.0) is None
        act = a.observe(queue_depth=1, p99_token_s=2.0)
        assert act["action"] == "scale_up" and act["replica"] == 3
        # hot p99 also vetoes relief
        a2 = self._scaler(tmp_path, queue_per_replica=100,
                          p99_high_s=0.5)[1]
        assert a2.observe(queue_depth=0, p99_token_s=2.0) is None
        assert a2.streaks["down"] == 0

    def test_queue_depth_defaults_to_journal_pending(self, tmp_path):
        j, a = self._scaler(tmp_path, queue_per_replica=1,
                            scale_after=1, cooldown_windows=0)
        j.mark_draining(3)
        j.submit_all([Request([1], 1, id=f"q{i}") for i in range(9)])
        act = a.observe()
        assert act["action"] == "scale_up"
        assert act["queue_depth"] == 9

    def test_standby_pool_mode_serves_after_activation(
        self, lm, tmp_path
    ):
        """End-to-end slice of the autoscale loop in one process: a
        drain-marked standby polls in ``serve(until_complete=...)``
        without exiting; the autoscaler lifts its marker (scale-up)
        mid-poll; the standby re-derives its share and completes the
        stream bit-identically to a fresh oracle engine."""
        import threading

        from chainermn_tpu.serving import ReplicaAutoscaler

        model, params = lm
        j = RequestJournal(str(tmp_path))
        reqs = [Request(p, 3, id=f"s{i}")
                for i, p in enumerate(_prompts(17, 4))]
        j.submit_all(reqs)
        j.mark_draining(0)  # pool of 1, standby
        rep = DecodeReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, replica_index=0, n_replicas=1)
        out = {}

        def _serve():
            out["served"] = rep.serve(until_complete=len(reqs),
                                      timeout_s=30.0)

        t = threading.Thread(target=_serve)
        t.start()
        a = ReplicaAutoscaler(j, 1, scale_after=1, cooldown_windows=0,
                              queue_per_replica=1)
        assert a.observe()["action"] == "scale_up"  # queue 4 > 1*1
        t.join(timeout=60)
        assert not t.is_alive()
        assert len(out["served"]) == len(reqs)
        res = j.results()
        oracle = DecodeEngine(model, params, capacity=2, page_size=8)
        for r in reqs:
            want = oracle.generate(r.prompt, r.max_new_tokens)
            assert res[r.id]["tokens"] == want, r.id
        assert j.pending() == []


# ----------------------------------------------------------------------
# prefix-sharing KV cache (ISSUE 17)
# ----------------------------------------------------------------------
class TestPrefixSharing:
    def test_alias_admission_shares_pages(self):
        """A page-aligned prompt prefix registered by one slot admits a
        second slot ALIASING those pages — refcount 2, lengths start at
        the shared length, one fresh tail page only."""
        c = _cache()
        toks = list(range(8))  # two full pages at page_size 4
        a = c.admit(9)
        c.advance(a, 8)  # prompt prefilled
        assert c.register_prefix(a, toks) == 2  # prefix-closed chains
        m = c.lookup_prefix(toks + [9, 10])
        assert m == PrefixMatch(tuple(c._slot_pages[a][:2]), 8, False)
        used0 = c.used_pages
        b = c.admit(11, prefix=m)
        assert int(c.lengths[b]) == 8  # only the tail prefills
        assert c._slot_pages[b][:2] == c._slot_pages[a][:2]
        assert c.used_pages == used0 + 1  # one fresh page, not three
        assert all(int(c._refcounts[p]) == 2 for p in m.pages)
        c.check_invariants()

    def test_fully_matched_prompt_caps_and_copies_on_write(self):
        """An identical resubmitted prompt matches ALL its pages; the
        shared length caps one short (the tail prefill needs a token),
        which marks the final page copy-on-write: the reserve earmarked
        at admission absorbs the write and the original page — still
        read by the registrant — is never touched."""
        c = _cache()
        toks = list(range(8))
        a = c.admit(8)
        c.advance(a, 8)
        c.register_prefix(a, toks)
        m = c.lookup_prefix(toks)
        assert m.shared_len == 7 and m.cow
        b = c.admit(12, prefix=m)
        assert b in c._cow_reserve
        c.check_invariants()
        shared_last = c._slot_pages[b][1]
        assert shared_last == c._slot_pages[a][1]
        # position 7 lands in the still-shared page: the copy happens
        assert c.cow_for_write(b, 1) is True
        assert c._slot_pages[b][1] != shared_last
        assert int(c._refcounts[shared_last]) == 1  # a's again, alone
        c.advance(b, 1)
        c.check_invariants()
        # now private: no further copies on this slot
        assert c.cow_for_write(b, 1) is False

    def test_advance_into_shared_page_without_cow_trips(self):
        """The tripwire behind the bit-identity guarantee: accounting a
        write into a refcount>1 page without ``cow_for_write`` raises
        instead of corrupting another request's history."""
        c = _cache()
        toks = list(range(8))
        a = c.admit(8)
        c.advance(a, 8)
        c.register_prefix(a, toks)
        b = c.admit(12, prefix=c.lookup_prefix(toks))
        with pytest.raises(CacheAdmissionError, match="copy-on-write"):
            c.advance(b, 1)

    def test_release_frees_only_at_refcount_zero(self):
        """Shared pages survive their registrant's release (the alias
        still reads them) and return to the pool — with their index
        entries dropped — only when the LAST reader releases."""
        c = _cache()
        toks = list(range(8))
        a = c.admit(9)
        c.advance(a, 8)
        c.register_prefix(a, toks)
        b = c.admit(10, prefix=c.lookup_prefix(toks + [3]))
        shared = set(c._slot_pages[b][:2])
        c.release(a)
        assert all(int(c._refcounts[p]) == 1 for p in shared)
        assert not shared & set(c._free_pages)
        assert c.lookup_prefix(toks + [5]) is not None  # content live
        c.check_invariants()
        c.release(b)
        assert c.used_pages == 0
        assert c.lookup_prefix(toks + [5]) is None  # entries dropped
        c.check_invariants()

    def test_victim_never_holds_a_shared_page(self):
        """choose_victim is LIFO over UNSHARED slots only: with every
        active slot holding a refcount>1 page there is no victim (the
        batcher queues); an unshared slot is picked even when a shared
        one was admitted later."""
        c = _cache(capacity=3)
        toks = list(range(8))
        u = c.admit(5)  # private, admitted first
        c.advance(u, 5)
        a = c.admit(9)
        c.advance(a, 8)
        c.register_prefix(a, toks)
        b = c.admit(10, prefix=c.lookup_prefix(toks + [1]))
        # b is newest but aliases a's pages; a shares them too — only
        # u is evictable despite being oldest
        assert c.choose_victim() == u
        c.check_invariants()
        c.evict(u)
        assert c.choose_victim() is None  # all-shared: nobody evicts
        c.check_invariants()
        c.release(b)
        assert c.choose_victim() == a  # a's pages are private again

    def test_refcount_invariants_under_op_mix(self):
        """Churn: admit (aliased and cold), tail prefill with CoW,
        decode writes, release, evict — ``check_invariants`` (refcount
        == table multiplicity, conservation, victim-never-shared, index
        liveness) holds after EVERY op, and the drained pool is empty."""
        c = _cache(capacity=4, num_pages=24)
        rng = np.random.RandomState(3)
        base = [list(range(8)), list(range(40, 48))]
        live = set()
        for _ in range(160):
            op = rng.randint(3)
            if op == 0 and len(live) < c.capacity:
                prompt = (base[rng.randint(2)]
                          + rng.randint(0, VOCAB,
                                        1 + rng.randint(3)).tolist())
                total = len(prompt) + 4
                m = c.lookup_prefix(prompt)
                if c.can_admit(total, prefix=m):
                    s = c.admit(total, prefix=m)
                    start = int(c.lengths[s])
                    c.cow_for_write(s, len(prompt) - start)
                    c.advance(s, len(prompt) - start)
                    c.register_prefix(s, prompt)
                    live.add(s)
            elif op == 1 and live:
                s = sorted(live)[rng.randint(len(live))]
                room = (len(c._slot_pages[s]) * c.page_size
                        - int(c.lengths[s]))
                if room > 0:
                    c.cow_for_write(s, 1)
                    c.advance(s, 1)
            elif op == 2 and live:
                if rng.randint(2):
                    s = sorted(live)[rng.randint(len(live))]
                    c.release(s)
                    live.discard(s)
                else:
                    v = c.choose_victim()
                    if v is not None:
                        c.evict(v)
                        live.discard(v)
            c.check_invariants()
        for s in sorted(live):
            c.release(s)
        assert c.used_pages == 0
        c.check_invariants()

    def test_shared_serve_bit_identical_with_fewer_pages(self, lm):
        """The tentpole acceptance: a high-overlap serve with sharing
        ON is bit-identical to the sharing-OFF serve (the same
        programs) and the unbatched oracle's greedy decode, while the
        peak DISTINCT page count drops."""
        model, params = lm
        prompts = _shared_prompts(6)

        def serve(share):
            eng = DecodeEngine(model, params, capacity=3, page_size=8)
            b = ContinuousBatcher(eng, share_prefixes=share)
            for i, p in enumerate(prompts):
                b.submit(Request(p, 3 + i % 3, id=f"r{i}"))
            peak = 0
            while b.step():
                peak = max(peak, eng.cache.used_pages)
                eng.cache.check_invariants()
            return b, peak

        hot, peak_hot = serve(True)
        cold, peak_cold = serve(False)
        assert hot.prefix_hits >= 1 and cold.prefix_hits == 0
        assert hot.prefix_tokens_shared >= 8
        assert peak_hot < peak_cold
        solo = DecodeEngine(model, params, capacity=1, page_size=8)
        for rid in hot.finished:
            r1, r0 = hot.finished[rid], cold.finished[rid]
            assert r1.state == "done"
            assert r1.output == r0.output
            _assert_same_greedy(
                lm, r1.output,
                solo.generate(r1.prompt, r1.max_new_tokens))

    def test_checkpoint_round_trip_with_live_shared_pages(self):
        """state_dict/load_state_dict carry refcounts and the CoW
        reserve: a snapshot taken mid-share reloads with identical
        allocator state, a tampered refcount row refuses to load, and
        a legacy snapshot (no sharing keys) still loads with refcounts
        derived from table multiplicity."""
        c = _cache()
        toks = list(range(8))
        a = c.admit(9)
        c.advance(a, 8)
        c.register_prefix(a, toks)
        c.admit(8, prefix=c.lookup_prefix(toks))  # capped: live reserve
        sd = c.state_dict()
        c2 = _cache()
        c2.load_state_dict(sd)  # runs check_invariants itself
        np.testing.assert_array_equal(c2._refcounts, c._refcounts)
        assert c2._cow_reserve == c._cow_reserve
        np.testing.assert_array_equal(c2.block_tables, c.block_tables)
        assert c2.used_pages == c.used_pages
        bad = dict(sd)
        bad["page_refcounts"] = np.roll(sd["page_refcounts"], 1)
        with pytest.raises(ValueError, match="refcounts"):
            _cache().load_state_dict(bad)
        legacy = {k: v for k, v in sd.items()
                  if k not in ("page_refcounts", "cow_reserve")}
        c3 = _cache()
        c3.load_state_dict(legacy)
        # tables alone reconstruct the sharing (the reserve earmark is
        # a new-format refinement a legacy snapshot never carried)
        owned = {p for pages in c3._slot_pages.values() for p in pages}
        for p in owned:
            assert c3._refcounts[p] == c._refcounts[p]

    def test_reshard_kv_state_preserves_sharing(self):
        """reshard_kv_state re-cuts heads only: the host allocator state
        — refcounts and CoW reserves included — rides through a 2→1
        reshard and the merged cache passes invariants with the same
        sharing structure."""
        c = PagedKVCache(n_layers=LAYERS, n_heads=2, d_head=4,
                         capacity=2, page_size=4, pages_per_slot=4)
        toks = list(range(8))
        a = c.admit(9)
        c.advance(a, 8)
        c.register_prefix(a, toks)
        c.admit(8, prefix=c.lookup_prefix(toks))
        sd = c.state_dict()
        merged = reshard_kv_state([sd, sd], 1)
        big = PagedKVCache(n_layers=LAYERS, n_heads=4, d_head=4,
                           capacity=2, page_size=4, pages_per_slot=4)
        big.load_state_dict(merged[0])
        np.testing.assert_array_equal(big._refcounts, c._refcounts)
        assert big._cow_reserve == c._cow_reserve
        np.testing.assert_array_equal(big.block_tables, c.block_tables)

    def test_warm_start_re_registers_shared_prefixes(self, lm, tmp_path):
        """Journal replica warm start with shared prefixes: a replica
        preempted mid-share drains pages + refcounts; the rejoining
        replica adopts the in-flight requests, RE-REGISTERS their
        prompts (the index itself never snapshots), and the still-
        pending requests alias the restored pages — completing the
        stream bit-identically to a no-fault oracle."""
        model, params = lm
        comm = cmn.create_communicator("single_node")
        ckpt = cmn.create_multi_node_checkpointer(
            "share", comm, path=str(tmp_path / "ck"))
        j = RequestJournal(str(tmp_path / "j"))
        docs = [Request(p, 4, id=f"s{i}")
                for i, p in enumerate(_shared_prompts(4, seed=23))]
        j.submit_all(docs)
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        rep = DecodeReplica(eng, j, checkpointer=ckpt)
        assert rep.batcher.share_prefixes
        with inject_faults(
            [FaultSpec("serving.decode_step", "preempt", at=[2])]
        ):
            rep.serve()
        assert rep.drained
        ckpt.wait_until_finished()
        oracle_eng = DecodeEngine(model, params, capacity=2, page_size=8)
        oracle = {r.id: oracle_eng.generate(r.prompt, r.max_new_tokens)
                  for r in docs}
        eng2 = DecodeEngine(model, params, capacity=2, page_size=8)
        rep2 = DecodeReplica(eng2, j, checkpointer=ckpt)
        assert rep2.warm_start() is not None
        # adopted prompts re-indexed over the restored pages
        assert rep2.batcher.active
        assert eng2.cache._prefix_index
        eng2.cache.check_invariants()
        rep2.serve()
        # the pending claims aliased the restored pages
        assert rep2.batcher.prefix_hits >= 1
        res = j.results()
        for rid, want in oracle.items():
            assert res[rid]["tokens"] == want, rid


# ----------------------------------------------------------------------
# speculative decode (ISSUE 17)
# ----------------------------------------------------------------------
class TestSpeculative:
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_spec_serve_bit_identical(self, k, lm):
        """Greedy-exact acceptance makes the speculative transcript the
        plain transcript BY CONSTRUCTION: every committed token is a
        target argmax, so outputs are the unbatched oracle's greedy
        decode at any k (k=1 is the degenerate plain-decode control).
        The verify step is another program than the decode step: equal
        to rounding, hence ``_assert_same_greedy``, not ``==``."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        b = SpeculativeBatcher(eng, _draft_engine(eng), k=k)
        out = b.serve([Request(p, 2 + i % 4)
                       for i, p in enumerate(_prompts(61, 5))])
        assert b.verify_steps > 0
        solo = DecodeEngine(model, params, capacity=1, page_size=8)
        for r in out:
            assert r.state == "done", r
            _assert_same_greedy(
                lm, r.output, solo.generate(r.prompt, r.max_new_tokens))
        # both allocators drained clean and in lockstep
        for cache in (eng.cache, b.draft.cache):
            assert cache.used_pages == 0
            cache.check_invariants()

    def test_all_accepted_when_draft_equals_target(self, lm):
        """A draft that IS the target proposes exactly the target's
        argmax chain: every verifiable proposal accepted (rate 1.0) and
        the outputs still bit-identical."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        draft = DecodeEngine(model, params, capacity=2, page_size=8)
        b = SpeculativeBatcher(eng, draft, k=4)
        out = b.serve([Request(p, 6) for p in _prompts(62, 3)])
        assert b.tokens_proposed > 0
        assert b.acceptance_rate == 1.0
        solo = DecodeEngine(model, params, capacity=1, page_size=8)
        for r in out:
            _assert_same_greedy(
                lm, r.output, solo.generate(r.prompt, r.max_new_tokens))

    def test_all_rejected_zero_params_draft(self, lm):
        """The other extreme: a zeroed draft proposes a constant token
        the target (nearly) never emits — every verify step commits via
        the all-rejected path (one corrected token) and the outputs are
        STILL bit-identical; only the acceptance rate collapses."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        b = SpeculativeBatcher(eng, _draft_engine(eng, zero=True), k=4)
        out = b.serve([Request(p, 5) for p in _prompts(63, 3)])
        assert b.tokens_proposed > 0
        assert b.acceptance_rate < 0.5
        solo = DecodeEngine(model, params, capacity=1, page_size=8)
        for r in out:
            assert r.state == "done"
            _assert_same_greedy(
                lm, r.output, solo.generate(r.prompt, r.max_new_tokens))

    def test_eos_retires_inside_a_speculative_commit(self, lm):
        """An eos landing mid-commit truncates exactly where plain
        decode stops — speculative over-proposal never leaks tokens
        past the stop."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        probe = eng.generate([5, 9, 11], 6)
        eos, n = _first_fresh_token(probe[3:])
        eng2 = DecodeEngine(model, params, capacity=2, page_size=8)
        b = SpeculativeBatcher(eng2, _draft_engine(eng2), k=4)
        out = b.serve([Request([5, 9, 11], 6, eos_id=eos)])[0]
        assert out.state == "done"
        assert out.tokens == probe[3:3 + n]
        assert 1 < n <= 4  # inside the first commit of k=4, not its end

    def test_rollback_rewinds_lengths_only(self):
        c = _cache()
        s = c.admit(12)
        c.advance(s, 8)
        pages = list(c._slot_pages[s])
        c.rollback(s, 5)
        assert int(c.lengths[s]) == 5
        assert c._slot_pages[s] == pages  # reservation untouched
        c.advance(s, 3)  # stale positions simply overwritten
        c.check_invariants()
        with pytest.raises(ValueError, match="rollback"):
            c.rollback(s, 9)
        with pytest.raises(ValueError, match="rollback"):
            c.rollback(s, -1)

    def test_construction_validates_geometry_and_layout(self, lm):
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        with pytest.raises(ValueError, match="k must be"):
            SpeculativeBatcher(eng, _draft_engine(eng), k=0)
        dm = TransformerLM(vocab_size=VOCAB, d_model=16, n_heads=2,
                           n_layers=1, max_len=MAXLEN)
        dp = dm.init(
            {"params": jax.random.PRNGKey(7),
             "dropout": jax.random.PRNGKey(8)},
            jnp.zeros((1, 8), jnp.int32),
        )
        mismatched = DecodeEngine(dm, dp, capacity=2, page_size=4)
        with pytest.raises(ValueError, match="geometry"):
            SpeculativeBatcher(eng, mismatched, k=2)
        dense = DecodeEngine(model, params, capacity=2, page_size=8,
                             layout="dense")
        with pytest.raises(ValueError, match="paged"):
            SpeculativeBatcher(dense, _draft_engine(eng), k=2)

    def test_spec_verify_budget_pin(self, tp_setup):
        """The spec_verify_step ceiling: the k-row verify program runs
        the SAME 2 row-parallel psums per layer as single-token decode
        (the amortization that makes speculation pay on a latency-bound
        interconnect) — exact on the authored trace, zero partitioner
        insertions on the compiled program."""
        from chainermn_tpu.analysis import assert_attributed, enforce

        comm, model, params, specs = tp_setup
        eng = DecodeEngine(model, params, capacity=2, page_size=8,
                           comm=comm, param_specs=specs)
        tr = eng.collective_trace("verify", bucket=4)
        census = enforce("spec_verify_step", tr)
        assert census.get("all_reduce") == 2 * LAYERS  # exact
        rep = assert_attributed(tr, eng.compiled_text("verify", bucket=4),
                                name="spec_verify_step")
        assert rep["all_reduce"]["implicit"] == []
        assert rep["all_reduce"]["authored"] == 2 * LAYERS

    def test_warm_start_mirrors_draft_slots(self, lm, tmp_path):
        """A speculative replica preempted mid-burst drains its TARGET
        cache; the rejoining replica warm-starts it and
        ``mirror_adopted`` re-admits every adopted slot into the draft
        at the SAME slot id, re-prefilled to length lockstep — the
        resumed serve completes bit-identically to a plain oracle."""
        model, params = lm
        comm = cmn.create_communicator("single_node")
        ckpt = cmn.create_multi_node_checkpointer(
            "spec", comm, path=str(tmp_path / "ck"))
        j = RequestJournal(str(tmp_path / "j"))
        docs = [Request(p, 4, id=f"v{i}")
                for i, p in enumerate(_prompts(91, 4))]
        j.submit_all(docs)
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        spec = SpeculativeBatcher(eng, _draft_engine(eng), k=2)
        rep = DecodeReplica(eng, j, checkpointer=ckpt, batcher=spec)
        with inject_faults(
            [FaultSpec("serving.spec_verify", "preempt", at=[2])]
        ):
            rep.serve()
        assert rep.drained
        ckpt.wait_until_finished()
        oracle_eng = DecodeEngine(model, params, capacity=2, page_size=8)
        oracle = {r.id: oracle_eng.generate(r.prompt, r.max_new_tokens)
                  for r in docs}
        eng2 = DecodeEngine(model, params, capacity=2, page_size=8)
        spec2 = SpeculativeBatcher(eng2, _draft_engine(eng2), k=2)
        rep2 = DecodeReplica(eng2, j, checkpointer=ckpt, batcher=spec2)
        assert rep2.warm_start() is not None
        assert spec2.active  # adopted mid-flight
        for s in spec2.active:
            assert spec2.draft.cache.active[s]
            assert (int(spec2.draft.cache.lengths[s])
                    == int(eng2.cache.lengths[s]))  # lockstep restored
        rep2.serve()
        res = j.results()
        for rid, want in oracle.items():
            assert res[rid]["tokens"] == want, rid

    def test_batcher_injection_requires_same_engine(self, lm):
        model, params = lm
        eng = DecodeEngine(model, params, capacity=2, page_size=8)
        other = DecodeEngine(model, params, capacity=2, page_size=8)
        b = SpeculativeBatcher(other, _draft_engine(other), k=2)
        with pytest.raises(ValueError, match="engine"):
            DecodeReplica(eng, RequestJournal(tempfile.mkdtemp()),
                          batcher=b)


# ----------------------------------------------------------------------
# disaggregated prefill/decode: role pools + codec-streamed KV handoff
# ----------------------------------------------------------------------
def _bits(x):
    """Raw bytes of an array for 0-tolerance comparison (bf16 pages
    compare as bits, not floats — NaN payloads and signed zeros count)."""
    return np.ascontiguousarray(np.asarray(x)).view(np.uint8)


class TestDisaggregation:
    """ISSUE 18 acceptance: prefill-pool export -> codec wire ->
    decode-pool import is BIT-IDENTICAL to local prefill for the
    lossless codecs (cache dtype bf16, so ``none``/``bf16`` round-trip
    exactly), atomically published through the journal, and
    recoverable past a dead prefill replica (pool-scoped drains,
    orphan re-prefill)."""

    @pytest.mark.parametrize("codec", ["none", "bf16"])
    def test_handoff_bit_identical_to_local_prefill(self, codec, lm):
        """Export -> pack(codec) -> unpack -> import: the imported
        pages equal the exporter's at 0 tolerance, and decoding from
        them equals the unified single-engine serve token for token."""
        model, params = lm
        prompt = _prompts(33, 1, lo=9, hi=14)[0]
        max_new = 6
        pe = DecodeEngine(model, params, capacity=2, page_size=8)
        slot = pe.admit(pe.prompt_bucket(len(prompt)))
        logits = pe.prefill(slot, prompt)
        kv = pe.export_kv(slot)
        kv2, first = transfer_kv(kv, int(np.argmax(logits)), codec)
        de = DecodeEngine(model, params, capacity=2, page_size=8)
        b = ContinuousBatcher(de)
        r = Request(prompt, max_new, id="h")
        b.ingest(r, kv2, first)
        exp = list(pe.cache._slot_pages[slot])
        imp = list(de.cache._slot_pages[r.slot])[:len(exp)]
        np.testing.assert_array_equal(
            _bits(de.cache.k_pages[:, imp]),
            _bits(pe.cache.k_pages[:, exp]))
        np.testing.assert_array_equal(
            _bits(de.cache.v_pages[:, imp]),
            _bits(pe.cache.v_pages[:, exp]))
        b.run()
        oracle = DecodeEngine(model, params, capacity=1,
                              page_size=8).generate(prompt, max_new)
        assert b.finished["h"].output == oracle

    def test_int8_handoff_gated_by_greedy_agreement(self, lm_long):
        """The int8 codec is transfer-once (no next step for an
        error-feedback residual to ride), so its gate is MEASURED
        greedy-token agreement over >= 64 generated tokens against the
        unified oracle — an accuracy question, never a loss pin."""
        model, params = lm_long
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, VOCAB, 12).tolist()
        max_new = 64
        pe = DecodeEngine(model, params, capacity=1, page_size=8)
        slot = pe.admit(pe.prompt_bucket(len(prompt)))
        logits = pe.prefill(slot, prompt)
        kv = pe.export_kv(slot)
        kv2, first = transfer_kv(kv, int(np.argmax(logits)), "int8")
        de = DecodeEngine(model, params, capacity=1, page_size=8)
        b = ContinuousBatcher(de)
        r = Request(prompt, max_new, id="q")
        b.ingest(r, kv2, first)
        b.run()
        got = b.finished["q"].output
        want = DecodeEngine(model, params, capacity=1,
                            page_size=8).generate(prompt, max_new)
        assert len(want) - len(prompt) >= 64
        # greedy decode diverges PERMANENTLY at the first argmax flip,
        # so the gate is the exact-prefix length, not fraction
        # agreement.  Random-init logits are near-uniform — the
        # adversarial case for an argmax gate — and the quantized
        # handoff still carries >= 16 tokens exactly (28 measured).
        div = next((i for i, (a, e) in enumerate(zip(got, want))
                    if a != e), len(want))
        assert div - len(prompt) >= 16, (
            f"int8 KV handoff diverged after {div - len(prompt)} "
            f"greedy tokens (< 16) over a {len(want) - len(prompt)}"
            f"-token window"
        )
        agree = sum(int(a == e) for a, e in zip(got, want)) / len(want)
        assert agree >= 0.5  # post-divergence floor: not corrupted

    def test_import_validates_geometry(self, lm):
        model, params = lm
        pe = DecodeEngine(model, params, capacity=2, page_size=8)
        prompt = _prompts(21, 1, lo=5, hi=9)[0]
        slot = pe.admit(pe.prompt_bucket(len(prompt)))
        pe.prefill(slot, prompt)
        kv = pe.export_kv(slot)
        with pytest.raises(ValueError, match="page_size"):
            _cache(capacity=2, page_size=4).import_kv(kv, 32)
        de = DecodeEngine(model, params, capacity=2, page_size=8)
        with pytest.raises(ValueError, match="total_tokens"):
            de.cache.import_kv(kv, kv.length - 1)
        with pytest.raises(ValueError, match="dtype"):
            de.cache.import_kv(kv._replace(dtype="float32"), 32)
        with pytest.raises(ValueError, match="geometry"):
            de.cache.import_kv(
                kv._replace(k=kv.k[:, :, :, :2], v=kv.v[:, :, :, :2]),
                32)

    def test_allocator_invariants_after_import_churn(self, lm):
        """Import admits FRESH pages per handoff; an admit/import/
        release mix must keep the allocator's invariants and return the
        pool to empty — imports never leak or alias the exporter."""
        model, params = lm
        prompt = _prompts(41, 1, lo=9, hi=13)[0]
        pe = DecodeEngine(model, params, capacity=1, page_size=8)
        slot = pe.admit(pe.prompt_bucket(len(prompt)))
        logits = pe.prefill(slot, prompt)
        kv = pe.export_kv(slot)
        first = int(np.argmax(logits))
        de = DecodeEngine(model, params, capacity=2, page_size=8)
        total = len(prompt) + 6
        live = []
        for _ in range(8):
            kv2, _ = transfer_kv(kv, first, "none")
            live.append(de.cache.import_kv(kv2, total))
            de.cache.check_invariants()
            if len(live) == de.cache.capacity:
                de.cache.release(live.pop(0))
                de.cache.check_invariants()
        for s in live:
            de.cache.release(s)
        de.cache.check_invariants()
        assert de.cache.used_pages == 0

    def test_prefix_reregistration_on_import(self, lm):
        """The handoff's prefix chain re-registers against the IMPORTED
        pages, so a later request on the decode pool aliases them —
        prefix sharing survives the pool boundary without re-hashing
        or re-prefilling."""
        model, params = lm
        head = _prompts(55, 1, lo=8, hi=9)[0]  # exactly one page
        p1 = head + [1, 2, 3]
        p2 = head + [4, 5]
        pe = DecodeEngine(model, params, capacity=2, page_size=8)
        slot = pe.admit(pe.prompt_bucket(len(p1)))
        logits = pe.prefill(slot, p1)
        pe.cache.register_prefix(slot, p1)
        kv = pe.export_kv(slot)
        assert len(kv.prefix_chain) == 1  # the one full-page depth
        kv2, first = transfer_kv(kv, int(np.argmax(logits)), "bf16")
        de = DecodeEngine(model, params, capacity=2, page_size=8)
        b = ContinuousBatcher(de)
        r1 = Request(p1, 4, id="a")
        b.ingest(r1, kv2, first)
        m = de.cache.lookup_prefix(p2)
        assert m is not None and m.shared_len == 8
        r2 = Request(p2, 4, id="b")
        b.submit(r2)
        b.run()
        assert b.prefix_hits == 1
        assert r2.shared_len == 8
        sol = DecodeEngine(model, params, capacity=1, page_size=8)
        assert b.finished["a"].output == sol.generate(p1, 4)
        assert b.finished["b"].output == sol.generate(p2, 4)

    def test_pack_handoff_wire_bytes_exact_and_codec_validated(self):
        """The disclosed ``wire_bytes`` is EXACT: payload bytes plus 4
        per int8 scale (one absmax grid per layer per tensor) — the
        number ``attribute()`` prices and the bench fingerprints."""
        k = np.asarray(jnp.ones((2, 3, 4, 2, 2), jnp.bfloat16))
        kv = KVExport(k=k, v=k, length=10, page_size=4,
                      dtype="bfloat16", prefix_chain=())
        ph = pack_handoff(kv, 7, "bf16")
        assert ph.meta["wire_bytes"] == 2 * k.size * 2  # bf16: 2B each
        ph8 = pack_handoff(kv, 7, "int8")
        # 1 byte/elem + 4B per scale, 2 layers x 2 tensors = 4 scales
        assert ph8.meta["wire_bytes"] == 2 * k.size + 4 * 4
        kv2, first = unpack_handoff(ph)
        assert first == 7
        np.testing.assert_array_equal(_bits(kv2.k), _bits(k))
        with pytest.raises(ValueError, match="codec"):
            pack_handoff(kv, 0, "f32")

    def test_handoff_codec_path_issues_zero_collectives(self):
        """The handoff path's own pin: encode/decode are jnp-pure casts
        — a codec that grew a collective (say, a global absmax pmax)
        would put KV transfer on the interconnect's critical path."""
        from chainermn_tpu.analysis import trace_collectives
        from chainermn_tpu.comm_wire.codecs import (
            decode_buffer,
            encode_buffer,
        )

        def roundtrip(x):
            a = decode_buffer(encode_buffer(x, "bf16"))
            c = decode_buffer(encode_buffer(x, "int8"))
            return a.astype(jnp.float32) + c.astype(jnp.float32)

        tr = trace_collectives(roundtrip, jnp.ones((4, 16), jnp.bfloat16))
        assert tr.census() == {}

    def test_kv_spans_priced_by_attribute(self, lm):
        """``kv.export``/``kv.ship``/``kv.import`` spans carry exact
        byte counts and ``kv_transfer_points`` prices each leg —
        bytes, achieved B/s, duration."""
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability.attribute import (
            kv_transfer_points,
        )

        model, params = lm
        tel = obs.Telemetry(label="kv-price")
        obs.install(tel)
        try:
            pe = DecodeEngine(model, params, capacity=1, page_size=8)
            prompt = _prompts(25, 1, lo=5, hi=9)[0]
            slot = pe.admit(pe.prompt_bucket(len(prompt)))
            logits = pe.prefill(slot, prompt)
            kv = pe.export_kv(slot)
            kv2, _first = transfer_kv(kv, int(np.argmax(logits)), "bf16")
            de = DecodeEngine(model, params, capacity=1, page_size=8)
            de.ingest_kv(kv2, len(prompt) + 4)
        finally:
            obs.install(None)
        pts = kv_transfer_points(tel.timeline)
        by = {p[0]: p for p in pts}
        assert set(by) == {"kv.export", "kv.ship", "kv.import"}
        # bf16 wire over a bf16 cache: wire bytes == the raw buffer
        assert by["kv.ship"][1] == kv.k.nbytes + kv.v.nbytes
        for _name, nbytes, _rate, dur in pts:
            assert nbytes > 0
            assert dur >= 0.0

    def test_disagg_serve_bit_identical_and_handoffs_cleared(
            self, lm, tmp_path):
        """The role-pool round trip through the journal: prefill pool
        publishes, decode pool ingests, every output equals the
        unified oracle at 0 tolerance — and consumed handoffs are
        cleared once their results exist."""
        model, params = lm
        j = RequestJournal(str(tmp_path))
        docs = [Request(p, 4, id=f"d{i}")
                for i, p in enumerate(_prompts(71, 4))]
        j.submit_all(docs)
        pr = PrefillReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, codec="bf16")
        assert pr.serve() == 4
        assert sorted(j.handoffs()) == sorted(r.id for r in docs)
        assert pr.wire_bytes > 0
        dr = DisaggDecodeReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, handoff_timeout_s=60.0)
        dr.serve(until_complete=4, timeout_s=120.0)
        assert dr.ingested == 4 and dr.local_prefills == 0
        res = j.results()
        sol = DecodeEngine(model, params, capacity=1, page_size=8)
        for r in docs:
            assert res[r.id]["tokens"] == sol.generate(
                r.prompt, r.max_new_tokens), r.id
        assert j.handoffs() == []  # hygiene: consumed == cleared

    def test_orphaned_handoff_reprefilled_bit_identical(
            self, lm, tmp_path):
        """A handoff that never appears (its prefill replica died
        before publishing) falls back to LOCAL prefill past
        ``handoff_timeout_s`` — greedy replay from the prompt, so the
        stream still completes bit-identically with no prefill pool at
        all."""
        model, params = lm
        j = RequestJournal(str(tmp_path))
        docs = [Request(p, 3, id=f"o{i}")
                for i, p in enumerate(_prompts(81, 3))]
        j.submit_all(docs)
        dr = DisaggDecodeReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, handoff_timeout_s=0.0)
        dr.serve(until_complete=3, timeout_s=120.0)
        assert dr.local_prefills == 3 and dr.ingested == 0
        res = j.results()
        sol = DecodeEngine(model, params, capacity=1, page_size=8)
        for r in docs:
            assert res[r.id]["tokens"] == sol.generate(
                r.prompt, r.max_new_tokens), r.id

    def test_dead_prefill_share_rederives_on_pool_drain(
            self, lm, tmp_path):
        """Marking a prefill replica draining (pool="prefill")
        re-routes its unpublished share onto the healthy prefill
        replicas — the same claim algebra the decode pool uses, scoped
        to the prefill marker namespace."""
        model, params = lm
        j = RequestJournal(str(tmp_path))
        docs = [Request(p, 2, id=f"s{i}")
                for i, p in enumerate(_prompts(61, 4))]
        j.submit_all(docs)
        p1 = PrefillReplica(
            DecodeEngine(model, params, capacity=2, page_size=8),
            j, replica_index=1, n_replicas=2)
        assert p1.serve() == 2  # its own share: seq 1 and 3
        assert len(j.handoffs()) == 2
        j.mark_draining(0, pool="prefill")
        assert p1.serve() == 4  # re-derived the dead replica's share
        assert sorted(j.handoffs()) == sorted(r.id for r in docs)

    def test_pool_scoped_drain_markers_are_disjoint(self, tmp_path):
        """Prefill-pool drains must not re-route decode-pool claims
        (and vice versa): the marker namespaces are disjoint by
        construction, and a pool name that could collide with the
        default digit namespace is rejected."""
        j = RequestJournal(str(tmp_path))
        j.mark_draining(0, pool="prefill")
        assert j.draining() == []
        assert j.draining(pool="prefill") == [0]
        j.mark_draining(1)
        assert j.draining() == [1]
        assert j.draining(pool="prefill") == [0]
        j.clear_draining(0, pool="prefill")
        assert j.draining(pool="prefill") == []
        assert j.draining() == [1]
        with pytest.raises(ValueError, match="alphabetic"):
            j.mark_draining(0, pool="pre_fill")

    def test_oversize_request_fails_loudly_in_prefill_pool(
            self, lm, tmp_path):
        """A request no decode-pool engine could ever admit fails
        LOUDLY at the prefill pool (result written, stream not
        wedged) — the unified replica's contract, kept across the
        split."""
        model, params = lm
        j = RequestJournal(str(tmp_path))
        j.submit_all([Request(list(range(5)), 500, id="big"),
                      Request([1, 2, 3], 2, id="ok")])
        pr = PrefillReplica(
            DecodeEngine(model, params, capacity=2, page_size=8), j)
        assert pr.serve() == 1  # "ok" published; "big" failed loudly
        res = j.results()
        assert res["big"]["state"] == "failed"
        assert "max_total" in res["big"]["error"]
        assert j.handoffs() == ["ok"]

    def test_ttft_splits_into_queue_plus_prefill(self, lm):
        """``serving.ttft`` decomposes into ``.queue`` (submit ->
        prefill start) + ``.prefill`` (prefill start -> first token):
        same timestamps, so the single-request algebra is exact — and
        under a capacity-1 backlog the wait lands in the QUEUE term,
        the split disaggregation exists to expose."""
        model, params = lm
        eng = DecodeEngine(model, params, capacity=1, page_size=8)
        b = ContinuousBatcher(eng)
        b.serve([Request(p, 3, id=f"t{i}")
                 for i, p in enumerate(_prompts(13, 3))])
        rep = b.latency_report()
        for key in ("serving.ttft", "serving.ttft.queue",
                    "serving.ttft.prefill"):
            assert rep[key]["n"] == 3, key
        assert rep["serving.ttft.queue"]["p99_ms"] > 0
        b2 = ContinuousBatcher(
            DecodeEngine(model, params, capacity=1, page_size=8))
        b2.serve([Request([5, 4, 3], 2, id="solo")])
        r2 = b2.latency_report()
        assert r2["serving.ttft"]["p50_ms"] == pytest.approx(
            r2["serving.ttft.queue"]["p50_ms"]
            + r2["serving.ttft.prefill"]["p50_ms"], abs=1e-3)

    def test_dense_oracle_and_bad_codec_rejected(self, lm, tmp_path):
        model, params = lm
        dense = DecodeEngine(model, params, capacity=2, layout="dense")
        j = RequestJournal(str(tmp_path))
        with pytest.raises(ValueError, match="dense"):
            PrefillReplica(dense, j)
        with pytest.raises(ValueError, match="dense"):
            DisaggDecodeReplica(dense, j)
        with pytest.raises(ValueError, match="paged-layout"):
            dense.export_kv(0)
        paged = DecodeEngine(model, params, capacity=2, page_size=8)
        with pytest.raises(ValueError, match="codec"):
            PrefillReplica(paged, j, codec="zstd")

    def test_pending_memoized_by_directory_signature(self, tmp_path):
        """ISSUE 18 bugfix pin: ``pending()`` rescans only when the
        req/res name signature changes — replicas poll it every round,
        and the old always-rescan turned the poll loop O(requests) in
        json loads."""
        j = RequestJournal(str(tmp_path))
        j.submit_all([Request([1, 2], 2, id=f"m{i}") for i in range(3)])
        base = j._pending_scans
        assert len(j.pending()) == 3
        j.pending()
        j.pending()
        assert j._pending_scans == base + 1  # repeats hit the memo
        j.submit(Request([3], 1, id="m3"))
        assert len(j.pending()) == 4
        assert j._pending_scans == base + 2  # new request -> rescan
        j.write_result(Request([1, 2], 2, id="m0"))
        assert len(j.pending()) == 3
        assert j._pending_scans == base + 3  # new result -> rescan
        j.pending()
        assert j._pending_scans == base + 3


# ----------------------------------------------------------------------
# mnlint: serving is NOT part of the sanctioned comm layer
# ----------------------------------------------------------------------
class TestServingLint:
    """ISSUE 13 satellite: the serving tier routes every collective
    through the audited wrappers (``parallel``/``functions.collectives``
    layers) — it is NOT sanctioned for raw ``lax.psum``-family calls,
    and the subsystem self-lints clean under the repo gate."""

    def test_serving_is_not_sanctioned(self):
        from chainermn_tpu.analysis.lint import SANCTIONED

        assert not any(
            p.startswith("chainermn_tpu/serving") for p in SANCTIONED
        ), "serving/ must never join the raw-psum sanctioned list"

    def test_serving_modules_lint_clean(self):
        from chainermn_tpu.analysis.lint import repo_root, run_lint

        root = repo_root()
        target = os.path.join(root, "chainermn_tpu", "serving")
        violations = run_lint([target], root=root)
        assert violations == [], "\n".join(
            f"{v.path}:{v.line}: {v.rule}: {v.message}"
            for v in violations
        )

    def test_raw_psum_in_serving_would_be_flagged(self, tmp_path):
        """Behavioral pin of the not-sanctioned claim: a raw collective
        dropped into a serving module trips the repo gate."""
        from chainermn_tpu.analysis.lint import run_lint

        bad = tmp_path / "chainermn_tpu" / "serving" / "sneaky.py"
        bad.parent.mkdir(parents=True)
        bad.write_text(
            "import jax.lax\n"
            "def f(x):\n"
            "    return jax.lax.psum(x, 'tp')\n"
        )
        violations = run_lint([str(bad)], root=str(tmp_path))
        assert [v.rule for v in violations] == ["raw-collective"]
