"""Paged KV cache backend: allocator invariants, copy-on-write prefix
sharing, paged-vs-dense bit-identity (model level and engine level), page
admission gating / preemption, paged kv_snapshot resume, and the
deprecation shims over the old free-function API."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import RolloutConfig
from repro.configs import get_config
from repro.core.rollout import RolloutEngine
from repro.core.trajectory import Trajectory
from repro.data.tasks import AdditionTask, EOS
from repro.models import model as M
from repro.sampling import kv_cache as kvc

CFG = get_config("tiny")
PARAMS = M.init_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# page allocator + COW unit tests
# ---------------------------------------------------------------------------

def _backend(pool=4, max_len=32, ps=8, npg=0):
    return kvc.PagedCache(CFG, pool=pool, max_len=max_len, page_size=ps,
                          num_pages=npg)


def test_allocator_exhaustion_and_free():
    b = _backend(pool=2, max_len=32, ps=8, npg=4)
    assert b.free_page_count() == 4
    b.alloc_slot_prefix(0, 24)                 # 3 pages
    assert b.free_page_count() == 1
    with pytest.raises(kvc.PageExhausted):
        b.alloc_slot_prefix(1, 17)             # needs 3, only 1 free
    assert b.free_page_count() == 1, "failed alloc must not leak pages"
    b.free_slot(0)
    assert b.free_page_count() == 4
    assert (b.refcount == 0).all()
    assert (b.block_table == b.num_pages).all()


def test_grow_dry_run_on_exhaustion():
    b = _backend(pool=2, max_len=32, ps=8, npg=4)
    b.alloc_slot_prefix(0, 24)                 # 3 pages
    b.alloc_slot_prefix(1, 8)                  # 1 page
    copies = []
    # slot 1 wants pages for [8, 24) -> 2 more pages, 0 free: must refuse
    # WITHOUT mutating, so the caller can preempt and retry
    assert not b.grow(1, 24, 8, copies)
    assert not copies and b.free_page_count() == 0
    b.free_slot(0)
    assert b.grow(1, 24, 8, copies)
    b.apply_copies(copies)


def test_cow_refcount():
    ps = 8
    b = _backend(pool=4, max_len=32, ps=ps)
    L = 6                                      # partial trailing page
    b.alloc_slot_prefix(0, L)
    b.share_slots(0, 1, L)
    assert b.refcount[b.block_table[0, 0]] == 2
    copies = []
    assert b.grow(1, L + 1, L, copies)
    assert copies, "write into a shared partial page must COW"
    b.apply_copies(copies)
    assert b.block_table[1, 0] != b.block_table[0, 0]
    assert b.refcount[b.block_table[0, 0]] == 1
    assert b.refcount[b.block_table[1, 0]] == 1
    b.free_slot(0)
    b.free_slot(1)
    assert b.free_page_count() == b.num_pages
    # page-aligned share: the writer's first page is FRESH, never COWed
    b.alloc_slot_prefix(0, ps)
    b.share_slots(0, 1, ps)
    copies = []
    assert b.grow(1, ps + 1, ps, copies) and not copies


@pytest.mark.parametrize("seed", range(8))
def test_allocator_refcount_invariants(seed):
    """Random admission orders: interleave alloc / share / grow / free on a
    4-slot pool and check the global page-accounting invariants after every
    operation, then full reclamation."""
    rng = np.random.default_rng(seed)
    ops = [(int(rng.integers(0, 4)), int(rng.integers(1, 31)))
           for _ in range(20)]
    b = _backend(pool=4, max_len=32, ps=8, npg=10)
    lens = [0] * 4

    def check():
        mapped = b.block_table[b.block_table < b.num_pages]
        # every mapped reference is counted, exactly
        ref = np.zeros(b.num_pages, np.int64)
        np.add.at(ref, mapped, 1)
        assert (ref == b.refcount).all()
        assert b.free_page_count() + len(np.unique(mapped)) == b.num_pages

    for slot, length in ops:
        length = min(length, 31)
        kind = rng.integers(0, 3)
        try:
            if kind == 0 or lens[slot] == 0:       # (re)alloc
                if lens[slot]:
                    b.free_slot(slot)
                    lens[slot] = 0
                b.alloc_slot_prefix(slot, length)
                lens[slot] = length
            elif kind == 1:                        # share onto another slot
                dst = int(rng.integers(0, 4))
                if dst != slot:
                    if lens[dst]:
                        b.free_slot(dst)
                    b.share_slots(slot, dst, lens[slot])
                    lens[dst] = lens[slot]
            else:                                  # grow one token
                upto = min(lens[slot] + 1, 31)
                copies = []
                if b.grow(slot, upto, lens[slot], copies):
                    b.apply_copies(copies)
                    lens[slot] = upto
        except kvc.PageExhausted:
            pass
        check()
    for s in range(4):
        if lens[s]:
            b.free_slot(s)
    assert b.free_page_count() == b.num_pages
    assert (b.refcount == 0).all()


# ---------------------------------------------------------------------------
# model-level bit identity and snapshots
# ---------------------------------------------------------------------------

def test_paged_matches_dense_model_decode():
    """Prefill + 6 decode steps: the paged cache path (block-table gather)
    must produce bit-identical logits to the dense cache path."""
    B, P, MAXLEN, PS = 3, 8, 32, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 24), 0,
                              CFG.vocab_size)
    lengths = jnp.array([P, P - 2, P - 1])
    dense = M.init_cache(CFG, B, MAXLEN)
    logits_d, dense = M.prefill(PARAMS, CFG, toks[:, :P], lengths, dense)

    b = _backend(pool=B, max_len=MAXLEN, ps=PS)
    scratch = M.init_cache(CFG, B, P)
    logits_p, scratch = M.prefill(PARAMS, CFG, toks[:, :P], lengths, scratch)
    np.testing.assert_array_equal(np.asarray(logits_d), np.asarray(logits_p))
    flat_pos = np.full((B, P), b.num_pages * PS, np.int32)
    for i in range(B):
        fp = b.alloc_slot_prefix(i, int(lengths[i]))
        flat_pos[i, :len(fp)] = fp
    b.cache = kvc.paged_insert_rows(b.cache, scratch, jnp.arange(B),
                                    jnp.arange(B), jnp.asarray(flat_pos))
    cl = lengths
    for s in range(6):
        copies = []
        for i in range(B):
            assert b.grow(i, int(cl[i]) + 1, int(cl[i]), copies)
        b.apply_copies(copies)
        tok = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(7), s),
                                 (B,), 0, CFG.vocab_size)
        ld, dense = M.decode_step(PARAMS, CFG, tok, dense, cl)
        lp, b.cache = M.decode_step(PARAMS, CFG, tok, b.cache, cl,
                                    paged=(b.block_table_device(), PS))
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        cl = cl + 1


def test_paged_write_full_slot_drops():
    """A write at cache_len == max_pages*page_size (slot fully written) must
    DROP instead of clamping into the slot's last physical page and
    corrupting logical position (max_pages-1)*page_size."""
    from repro.models.attention import paged_write_kv
    NP, ps, mp, KV, hd = 5, 8, 2, 2, 4
    pool = jnp.zeros((NP, ps, KV, hd))
    bt = jnp.array([[0, 1]], jnp.int32)            # fully mapped slot
    new = jnp.ones((1, 1, KV, hd))
    out = paged_write_kv(pool, new, bt, ps, jnp.array([mp * ps]))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pool))
    # an in-range write still lands (page 1, offset 0)
    out = paged_write_kv(pool, new, bt, ps, jnp.array([ps]))
    np.testing.assert_array_equal(np.asarray(out[1, 0]),
                                  np.ones((KV, hd), np.float32))


def test_paged_decode_pallas_wiring():
    """use_pallas=True routes the paged decode through the Pallas
    ``paged_decode_attn`` kernel (interpret mode on CPU) — the logits must
    match the gather-to-dense reference path."""
    B, P, MAXLEN, PS = 2, 8, 32, 8
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, P), 0,
                              CFG.vocab_size)
    lengths = jnp.array([P, P - 3])
    b = _backend(pool=B, max_len=MAXLEN, ps=PS)
    scratch = M.init_cache(CFG, B, P)
    _, scratch = M.prefill(PARAMS, CFG, toks, lengths, scratch)
    flat_pos = np.full((B, P), b.num_pages * PS, np.int32)
    for i in range(B):
        fp = b.alloc_slot_prefix(i, int(lengths[i]))
        flat_pos[i, :len(fp)] = fp
    b.cache = kvc.paged_insert_rows(b.cache, scratch, jnp.arange(B),
                                    jnp.arange(B), jnp.asarray(flat_pos))
    copies = []
    for i in range(B):
        assert b.grow(i, int(lengths[i]) + 1, int(lengths[i]), copies)
    b.apply_copies(copies)
    tok = jnp.array([3, 7])
    paged = (b.block_table_device(), PS)
    ref, _ = M.decode_step(PARAMS, CFG, tok, b.cache, lengths, paged=paged)
    out, _ = M.decode_step(PARAMS, CFG, tok, b.cache, lengths, paged=paged,
                           use_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_dense_decode_pallas_wiring():
    """use_pallas=True routes the dense-cache decode through the Pallas
    ``decode_attn`` kernel (interpret mode on CPU): logits and the written
    cache (later layers' K/V follow earlier layers' attention) must match
    the XLA decode path."""
    B, P, MAXLEN = 2, 8, 32
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, P), 0,
                              CFG.vocab_size)
    lengths = jnp.array([P, P - 3])
    _, cache = M.prefill(PARAMS, CFG, toks, lengths,
                         M.init_cache(CFG, B, MAXLEN))
    tok = jnp.array([3, 7])
    ref, ref_cache = M.decode_step(PARAMS, CFG, tok, cache, lengths)
    out, out_cache = M.decode_step(PARAMS, CFG, tok, cache, lengths,
                                   use_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    for a, b in zip(jax.tree.leaves(out_cache), jax.tree.leaves(ref_cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_paged_snapshot_roundtrip():
    """extract_snapshot returns a page-list blob (never densified) that
    insert_snapshot restores bit-identically into a fresh pool."""
    B, P, PS = 2, 8, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0,
                              CFG.vocab_size)
    lengths = jnp.array([P, P - 3])
    b = _backend(pool=B, max_len=32, ps=PS)
    scratch = M.init_cache(CFG, B, P)
    _, scratch = M.prefill(PARAMS, CFG, toks, lengths, scratch)
    flat_pos = np.full((B, P), b.num_pages * PS, np.int32)
    for i in range(B):
        fp = b.alloc_slot_prefix(i, int(lengths[i]))
        flat_pos[i, :len(fp)] = fp
    b.cache = kvc.paged_insert_rows(b.cache, scratch, jnp.arange(B),
                                    jnp.arange(B), jnp.asarray(flat_pos))
    snap = b.extract_snapshot(1)
    assert isinstance(snap, dict) and "page_count" in snap

    b2 = _backend(pool=3, max_len=32, ps=PS)
    b2.insert_snapshot(snap, 2)
    tok = jnp.full((3,), 5)
    cl1 = int(lengths[1])
    want, _ = M.decode_step(PARAMS, CFG, jnp.full((B,), 5), b.cache,
                            lengths, paged=(b.block_table_device(), PS))
    got, _ = M.decode_step(PARAMS, CFG, tok, b2.cache,
                           jnp.array([1, 1, cl1]),
                           paged=(b2.block_table_device(), PS))
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[2]))


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def _run(mode, backend, *, seed=9, key=42, **kw):
    task = AdditionTask(max_value=20, seed=seed)
    kw.setdefault("decode_chunk", 4)
    ro = RolloutConfig(batch_size=3, group_size=2, max_prompt_len=16,
                       max_response_len=24, concurrency=4, mode=mode,
                       kv_backend=backend, **kw)
    eng = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS)
    return eng.collect(PARAMS, 0, jax.random.PRNGKey(key))


def _tmap(groups):
    return {(g.group_id, t.sample_idx): t
            for g in groups for t in g.trajectories}


@pytest.mark.parametrize("mode", ["sync", "copris"])
def test_engine_paged_equals_dense(mode):
    """kv_backend='paged' produces bit-identical trajectory CONTENT to
    'dense' (per-trajectory PRNG streams make content independent of the
    admission path); sync mode additionally pins the trajectory SET."""
    gd, _ = _run(mode, "dense")
    gp, sp = _run(mode, "paged", kv_page_size=16)
    base, got = _tmap(gd), _tmap(gp)
    if mode == "sync":
        assert set(base) == set(got)
    common = set(base) & set(got)
    assert common
    for k in common:
        assert base[k].response_tokens == got[k].response_tokens
        assert base[k].behaviour_logps == got[k].behaviour_logps
    # prefix sharing fired and the accounting is closed
    assert sp["shared_prefill_rows"] > 0
    assert sp["prefill_rows"] + sp["shared_prefill_rows"] == sp["prefill_count"]


@pytest.mark.parametrize("seed,key,ps,chunk", [(9, 42, 8, 2), (5, 7, 16, 6)])
def test_engine_paged_equals_dense_randomized(seed, key, ps, chunk):
    """Property flavour of the above: different prompt mixes, page sizes and
    chunk lengths permute the admission order; content must not move."""
    gd, _ = _run("copris", "dense", seed=seed, key=key, decode_chunk=chunk)
    gp, _ = _run("copris", "paged", seed=seed, key=key, decode_chunk=chunk,
                 kv_page_size=ps)
    base, got = _tmap(gd), _tmap(gp)
    common = set(base) & set(got)
    assert common
    for k in common:
        assert base[k].response_tokens == got[k].response_tokens


def test_one_prefill_per_group():
    """Prefix sharing: one prefill ROW feeds all G samples of a group. In
    sync mode all B*G spawns land in one initial fill, so rows == B and
    shared == B*(G-1)."""
    _, st_ = _run("sync", "paged", kv_page_size=16)
    assert st_["prefill_rows"] == 3
    assert st_["shared_prefill_rows"] == 3
    assert st_["prefill_count"] == 6


def test_admission_pressure_still_completes():
    """A page pool barely larger than one trajectory forces admission
    blocking and mid-stage preemption — every group must still complete."""
    gp, st_ = _run("copris", "paged", kv_page_size=8, kv_num_pages=8)
    assert len(gp) == 3 and all(len(g.trajectories) == 2 for g in gp)
    for g in gp:
        for t in g.trajectories:
            t.check_invariants()
    assert st_["admission_blocked"] > 0
    assert st_["page_preemptions"] > 0


def test_preempt_flushes_pending_cow_before_snapshot():
    """Deterministic repro of the COW-vs-snapshot ordering hazard: a slot
    COWs a shared partial page (its block table now points at the copy
    DESTINATION, whose batched scatter has not landed yet) and is then
    preempted in the same _prepare_decode_pages round. _preempt_slot must
    flush the pending copies before extract_snapshot, or the snapshot
    captures the uninitialized destination page."""
    L, PS = 6, 8                                   # partial trailing page
    task = AdditionTask(max_value=20, seed=3)
    ro = RolloutConfig(batch_size=1, group_size=2, max_prompt_len=16,
                       max_response_len=24, concurrency=4, mode="copris",
                       resume_strategy="kv_snapshot", kv_backend="paged",
                       kv_page_size=PS)
    eng = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS)
    b = eng.backend
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, L), 0,
                              CFG.vocab_size)
    scratch = M.init_cache(CFG, 1, L)
    _, scratch = M.prefill(PARAMS, CFG, toks, jnp.array([L]), scratch)
    fp = b.alloc_slot_prefix(0, L)
    b.cache = kvc.paged_insert_rows(b.cache, scratch, jnp.asarray([0]),
                                    jnp.asarray([0]), jnp.asarray(fp[None]))
    b.share_slots(0, 1, L)                         # prefix-shared group member
    copies = []
    assert b.grow(1, L + 1, L, copies) and copies  # COW queued, NOT applied

    traj = Trajectory(group_id=0, sample_idx=1,
                      prompt_tokens=np.asarray(toks[0], np.int32))
    eng.slots[1] = traj
    eng.cache_len[1] = L
    eng.last_token[1] = 5
    eng._stats = dict(page_preemptions=0)

    class _Sched:
        def requeue(self, t):
            pass

    eng._preempt_slot(1, _Sched(), copies)
    assert not copies, "pending COW batch must be flushed, not carried"
    assert traj.kv_snapshot is not None and traj.snap_cache_len == L

    # restoring the snapshot must reproduce the shared source KV bit-exactly
    b2 = _backend(pool=2, max_len=eng.max_len, ps=PS)
    b2.insert_snapshot(traj.kv_snapshot, 0)
    want, _ = M.decode_step(PARAMS, CFG, jnp.full((eng.pool,), 4), b.cache,
                            jnp.full((eng.pool,), L, jnp.int32),
                            paged=(b.block_table_device(), PS))
    got, _ = M.decode_step(PARAMS, CFG, jnp.full((2,), 4), b2.cache,
                           jnp.full((2,), L, jnp.int32),
                           paged=(b2.block_table_device(), PS))
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))


def test_preemption_kv_snapshot_bitexact():
    """Paged + resume_strategy='kv_snapshot' + mid-stage preemption under
    page pressure, with prefix sharing live: a victim preempted in the same
    _prepare_decode_pages round it COW'd must snapshot the FLUSHED pages,
    not un-applied copy destinations — resumed trajectories stay
    bit-identical to the dense run."""
    gd, _ = _run("copris", "dense", resume_strategy="kv_snapshot")
    gp, st_ = _run("copris", "paged", kv_page_size=8, kv_num_pages=8,
                   resume_strategy="kv_snapshot")
    assert st_["page_preemptions"] > 0
    assert st_["shared_prefill_rows"] > 0
    base, got = _tmap(gd), _tmap(gp)
    common = set(base) & set(got)
    assert common
    for k in common:
        assert base[k].response_tokens == got[k].response_tokens
        assert base[k].behaviour_logps == got[k].behaviour_logps


def test_paged_kv_snapshot_resume():
    """resume_strategy='kv_snapshot' on the paged backend: evictions carry
    page-list blobs (dict, never a dense slice) and later stages restore
    them."""
    task = AdditionTask(max_value=20, seed=11)
    # eviction by construction: twice the slots B*G needs, and a length cap
    # high enough that random EOS ends responses at different steps — the
    # stage closes on its B-th group while other slots are mid-response
    ro = RolloutConfig(batch_size=3, group_size=2, max_prompt_len=16,
                       max_response_len=128, concurrency=12, mode="copris",
                       resume_strategy="kv_snapshot", kv_backend="paged",
                       kv_page_size=16)
    eng = RolloutEngine(CFG, ro, task.sample_prompt, eos_id=EOS)
    _, s1 = eng.collect(PARAMS, 0, jax.random.PRNGKey(3))
    assert s1["evicted"] > 0
    snaps = [t for g in eng.buffer.groups() for t in g.trajectories
             if t.kv_snapshot is not None]
    assert snaps
    assert all(isinstance(t.kv_snapshot, dict)
               and "page_count" in t.kv_snapshot for t in snaps)
    _, s2 = eng.collect(PARAMS, 1, jax.random.PRNGKey(4))
    assert s2.get("snapshot_resumes", 0) > 0


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_free_function_shims_warn_and_work():
    cache = M.init_cache(CFG, 3, 16)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        snap = kvc.extract_slots(cache, jnp.asarray([1]))
        cache = kvc.insert_slots(cache, snap, jnp.asarray([2]))
        cache = kvc.zero_slots(cache, jnp.asarray([0]))
    names = {str(x.message) for x in w
             if issubclass(x.category, DeprecationWarning)}
    assert any("extract_slots" in n for n in names)
    assert any("insert_slots" in n for n in names)
    assert any("zero_slots" in n for n in names)
