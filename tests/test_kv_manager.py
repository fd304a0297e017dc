"""The KV manager alone (engine/kv_manager.py): what a sequence holds in
the pools, the prefix rule, the one rollback and the one refusal, driven
as the scheduler drives them and with no device: this file imports no
JAX, compiles nothing and runs in milliseconds.

One set of cases over the three kinds of hold: one pool; a full and a
window pool (Mellum 2's 3 : 1, a window of 4 blocks); one pool and a
sparse snapshot pool (a linear-attention state)."""

import subprocess
import sys
from types import SimpleNamespace

import pytest

from dynamo_tpu.engine.allocator import sequence_block_hashes
from dynamo_tpu.engine.kv_manager import KvManager
from dynamo_tpu.models.config import ModelConfig

BS, W, CHUNK = 4, 16, 8
KINDS = ("one", "window", "snapshots")
MODELS = {
    "one": ModelConfig.tiny(num_layers=4),
    "window": ModelConfig.tiny(
        num_layers=4, layer_windows=(W, W, W, 0), window_kv_pool=True),
    "snapshots": ModelConfig.tiny(
        num_layers=4, layer_ops=("linear", "linear", "linear", "attn")),
    "conv": ModelConfig.tiny(
        num_layers=4, layer_ops=("conv", "conv", "attn", "attn")),
}
#: an option each that one paged cache under one table serves alone,
#: with the word its refusal names it by
OPTIONS = {
    "spec_gamma": (dict(spec_gamma=2), "spec_gamma"),
    "ring": (dict(ring_prefill_threshold=64), "ring prefill"),
    "mesh": (dict(mesh=object()), "mesh"),
    "mirror": (dict(), "the multi-host mirror"),
    "tiers": (dict(host_cache_blocks=8), "KV tiers"),
    "adapters": (dict(adapters=("a:4",)), "adapters"),
    "int8": (dict(kv_cache_dtype="int8"), "kv_cache_dtype=int8"),
}
CALLS = ("reshard", "export_device_chain (fleet prefix cache)",
         "prefill_extract (disaggregation)",
         "prefill_extract_stream (disaggregation)",
         "begin_remote (disaggregation)")
#: the words each kind's refusal says what its sequences hold in
WORDS = {"window": "window and full attention layers in two KV pools",
         "snapshots": "linear-attention layers", "conv": "conv layers"}


def _cfg(kind, **over):
    """What ``KvManager`` reads of an ``EngineConfig``."""
    base = dict(
        model=MODELS[kind], num_blocks=64, block_size=BS, max_batch_size=2,
        max_blocks_per_seq=32, prefill_chunk=CHUNK, mixed_step_budget=CHUNK,
        window_blocks=0, spec_gamma=0, ring_prefill_threshold=0, mesh=None,
        host_cache_blocks=0, disk_cache_blocks=0, adapters=(),
        kv_cache_dtype="model")
    return SimpleNamespace(**{**base, **over})


def _kv(kind, **over):
    return KvManager(_cfg(kind, **over), snapshot_rows=4)


def _prompt(seed, n):
    return [16 + (seed * 7919 + i * 31) % 480 for i in range(n)]


def _pools(kv):
    """Every pool's blocks by state, and the snapshot rows pinned."""
    return ([kv.allocator.state_counts()]
            + ([] if kv.window is None
               else [kv.window.allocator.state_counts()])
            + ([] if kv.snapshots is None else [dict(kv.snapshots._pins)]))


def _live(kv, tokens, release=True):
    """A sequence's life in the pools as the scheduler drives it: its
    reservation, its prefill chunk by chunk (provision, take a snapshot
    row where the hold wants one, commit), its prompt's end."""
    hold, history, _upload = kv.reserve(tokens, None)
    pos = history
    if kv.snapshots is not None:
        kv.restore_from(hold)
    while pos < len(tokens):
        take = kv.clip_take(hold, pos, min(CHUNK, len(tokens) - pos))
        assert kv.grow(hold, pos, pos + take)
        assert not kv.short(hold, pos + take)
        if kv.snapshots is not None:
            kv.snap_rows([(hold, pos + take)])
        pos += take
        if pos < len(tokens):
            kv.commit(hold, tokens, pos, chunk=True)
    kv.commit(hold, tokens, len(tokens))
    if release:
        kv.release(hold)
    return hold, history


def _forget(alloc, hashes):
    """``alloc``'s blocks of ``hashes`` hold other content now."""
    for h in hashes:
        b = alloc.claim(h)
        del alloc._by_hash[h]
        b.seq_hash = b.local_hash = None
        alloc.free([b])


@pytest.mark.parametrize("kind", KINDS)
def test_a_cold_reserve(kind):
    """A prompt nobody has seen: its tokens' blocks and one of headroom
    in the full pool, nothing committed, nothing to skip; in the window
    pool its first chunk's blocks only; tables as the step programs take
    them, an array or the pair."""
    kv = _kv(kind)
    tokens = _prompt(1, 21)
    hold, history, upload = kv.reserve(tokens, None)
    assert (history, upload, hold.committed) == (0, None, 0)
    assert len(hold.blocks) == kv.blocks_for(21) == (21 + BS) // BS + 1
    assert kv.allocator.state_counts()["used"] == len(hold.blocks)
    assert not kv.at_limit(hold) and hold.parent_hash is None
    tables = kv.tables(hold)
    if kind == "window":
        assert len(hold.wblocks) == CHUNK // BS and kv.short(hold, 21)
        full, window = tables
        assert list(window[:3]) == [b.idx for b in hold.wblocks] + [0]
        stacked = kv.stack_tables([hold], 2)
        assert stacked[1].shape == (2, 32) and not stacked[1][1].any()
        assert (stacked[0][0] == full).all()
    else:
        assert not kv.short(hold, 21) and kv.short(hold, 29)
        full = tables
        assert kv.stack_tables([hold, hold], 4).shape == (4, 32)
    assert list(full[:8]) == [b.idx for b in hold.blocks] + [0]
    assert kv.table_rows(hold)[0] is not full  # a fresh row a call
    kv.release(hold)
    assert hold.blocks == [] and hold.wblocks == []
    assert kv.usage() == (0.0, 0, 63 + (24 if kind == "window" else 0))


@pytest.mark.parametrize("kind,lost,hit", [
    ("one", (), 40),
    ("window", (), 40),            # the window pool backs the whole match
    ("window", (8,), 32),          # a hole in the last window: cut below it
    ("window", range(4, 10), 16),  # the tail is gone: back to what is whole
    ("window", range(10), 0),      # nothing left: the full match is void
    ("snapshots", (), 40),         # the prompt's last block has a snapshot
    ("snapshots", "fork", 0),      # a fork's match ends at a block without one
    ("snapshots", "evicted", 0),   # the row went to a newer snapshot
])
def test_a_repeat_hits_by_the_prefix_rule(kind, lost, hit):
    """The longest block boundary every part of the hold can serve: the
    full pool matches the same tokens every time, and what the window
    pool has lost, or which block has a snapshot, decides how many count.
    What a finished sequence committed stays hittable after release."""
    kv = _kv(kind)
    tokens = _prompt(2, 41)
    _live(kv, tokens)
    chain = [h for _l, h in sequence_block_hashes(tokens[:40], BS)]
    assert all(kv.allocator.has_hash(h) for h in chain)
    assert kv.allocator.state_counts()["used"] == 0
    matched = 40
    if lost == "fork":
        tokens, matched = tokens[:22] + _prompt(3, 9), 20
    elif lost == "evicted":
        for seed in range(4, 8):  # four rows, four newer snapshots
            _live(kv, _prompt(seed, 41))
    elif lost:
        _forget(kv.window.allocator, [chain[i] for i in lost])
    before = dict(kv.stats)
    hold, history, _upload = kv.reserve(tokens, None)
    assert history == hit and hold.committed * BS == (
        hit if kind == "window" else matched)
    delta = {k: v - before[k] for k, v in kv.stats.items() if v != before[k]}
    if kind == "one":
        assert kv.stats == {}
    elif kind == "window":
        assert hold.window_cut * BS == matched - hit
        assert delta == {k: v for k, v in (
            ("prefix_matched_tokens", 40),
            ("prefix_window_missed_tokens", 40 - hit)) if v}
        # the tail it claimed, behind Nones: nothing in front is held
        assert [b is not None for b in hold.wblocks[:hit // BS]] == (
            [False] * hold.wfloor + [True] * (hit // BS - hold.wfloor))
        assert kv.prefill_attrs(hold, history) == {
            "cut_by": "window" if hit < 40 else "full",
            "window_cut": 40 - hit}
    else:
        assert delta == {k: v for k, v in (
            ("prefix_matched_tokens", matched),
            ("prefix_unsnapshotted_tokens", matched - hit)) if v}
        # the chunk that reaches the match's end leaves a snapshot, and
        # so does the one that ends at the prompt's last full block
        want = {matched, len(tokens) // BS * BS} if hit < matched else set()
        assert set(hold.snap_points) == want
        assert (hold.restore_row >= 0) == (hit > 0)
        assert kv.prefill_attrs(hold, history) == {"restored": hit}
        assert kv.clip_take(hold, 16, 8) == (4 if lost == "fork" else 8)
    kv.release(hold)  # (a pinned row it never restored from goes back too)
    assert all(p.get("used", 0) == 0 for p in _pools(kv))


class _Tier:
    """The host tier's side of a reservation, recorded."""

    def __init__(self):
        self.reserved, self.unreserved = [], []

    def reserve_chain(self, hashes):
        self.reserved = list(hashes[:2])
        return self.reserved, ["data"] * len(self.reserved)

    def unreserve(self, hashes, data):
        self.unreserved = list(hashes)

    def on_evict(self, seq_hash, idx):
        pass


@pytest.mark.parametrize("kind,point", [
    *((kind, point) for kind in KINDS for point in ("full pool", "host tier")),
    ("window", "window pool")])
def test_a_failed_reserve_leaves_the_pools_as_it_found_them(kind, point):
    """Whatever a reservation claimed before a pool turned it down (a
    prefix hit's blocks in every pool, a pinned snapshot row, the host
    tier's chain) goes back through the one ``release``."""
    kv = _kv(kind)
    tier = None
    if point == "host tier":
        tier = _Tier()
        kv.attach_offload(tier)
    tokens = _prompt(5, 41)
    _live(kv, tokens)
    pool = kv.window.allocator if point == "window pool" else kv.allocator
    # the free list is taken (and, of the window pool, what the prompt
    # left cached in front of its last window): the rest can be hit
    taken = pool.allocate(len(pool._free) + 6 * (point == "window pool"))
    before = _pools(kv)
    assert kv.reserve(tokens + _prompt(6, 40), None, probe_host=True) is None
    assert _pools(kv) == before
    assert kv.why().startswith(
        "window pool" if point == "window pool" else "full pool")
    if tier is not None:
        assert tier.reserved and tier.unreserved == tier.reserved
    pool.free(taken)
    hold, history, _upload = kv.reserve(tokens, None)
    assert history == 40 and len(hold.blocks) == kv.blocks_for(41)


@pytest.mark.parametrize("kind", KINDS)
def test_grow_gives_back_behind_the_window_before_it_takes(kind):
    """A sequence advancing through a prompt of five windows: in the
    window pool it never holds more than a window and a chunk (and a
    block of slack), even with every other block of that pool taken: what
    falls behind its window is what its next chunk is given. In the full
    pool it holds its whole context; a pool with nothing to give says so
    by name and takes nothing."""
    kv = _kv(kind)
    tokens = _prompt(7, 5 * W + 1)
    hold, _history, _upload = kv.reserve(tokens, None)
    taken = []
    if kind == "window":
        alloc = kv.window.allocator
        taken = alloc.allocate(alloc.free_count - W // BS)
    for pos in range(0, len(tokens), CHUNK):
        end = min(pos + CHUNK, len(tokens))
        assert kv.grow(hold, pos, end), kv.why()
        held = sum(b is not None for b in hold.wblocks)
        assert held <= (W + CHUNK) // BS + 1
        kv.commit(hold, tokens, end, chunk=True)
    assert len(hold.blocks) == kv.blocks_for(len(tokens))
    if kind == "window":
        assert kv.step_attrs() == {"window_released": hold.wfloor} != {}
        assert kv.step_attrs() == {"window_released": 0}
        assert hold.wfloor == kv.window.first_seen(len(tokens) - 1)
        rows = [(hold, len(tokens))]
        kv.note_work(2, rows, [(8, 4)])
        assert kv.stats["kv_window_context_tokens"] == 2 * len(tokens)
        assert kv.stats["kv_window_resident_tokens"] == 2 * BS * (
            len(hold.wblocks) - hold.wfloor)
        assert 0 < kv.stats["attn_window_pages"] < kv.stats[
            "attn_window_context_pages"]
    else:
        assert kv.step_attrs() == {} and hold.wblocks == []
        kv.note_work(2, iter(()))
    # the decode side: a block more when the next token needs one
    n = len(hold.blocks)
    assert kv.grow(hold, len(tokens) - 1, n * BS)
    assert not kv.short(hold, n * BS) and kv.short(hold, n * BS + 1)
    assert kv.grow(hold, n * BS - 1, n * BS + 1)
    assert len(hold.blocks) == n + 1
    rest = kv.allocator.allocate(kv.allocator.free_count)
    assert not kv.grow(hold, n * BS, (n + 1) * BS + 1)
    assert kv.why().startswith("full pool exhausted")
    assert len(hold.blocks) == n + 1
    kv.allocator.free(rest)
    kv.release(hold)
    if taken:
        kv.window.allocator.free(taken)
    assert [p["used"] for p in _pools(kv)[:2] if "used" in p] == [0] * (
        2 if kind == "window" else 1)


@pytest.mark.parametrize("kind", KINDS)
def test_release_after_commit_leaves_the_blocks_hittable(kind):
    """Decode-side commits lag the token whose KV is not written yet; a
    release (a finish, a preemption) parks what was committed, still
    addressed by its content, and empties the hold for the next
    reservation."""
    kv = _kv(kind)
    tokens = _prompt(8, 30)
    hold, _history = _live(kv, tokens, release=False)
    assert hold.committed == 30 // BS
    tokens = tokens + _prompt(9, 2)  # two decoded tokens: 32, 31 written
    assert kv.grow(hold, 31, 33)
    kv.commit(hold, tokens, len(tokens) - 1)
    assert hold.committed == 7
    kv.commit(hold, tokens + [5], len(tokens))
    assert hold.committed == 8 and hold.parent_hash == hold.blocks[7].seq_hash
    kv.release(hold)
    kv.release(hold)  # (nothing left to give: a finish after an abort)
    assert (hold.blocks, hold.committed, hold.parent_hash) == ([], 0, None)
    again, history, _upload = kv.reserve(tokens + [5, 6], None)
    # (a sparse snapshot pool: the prompt's end at 28 has the snapshot)
    assert history == (28 if kind == "snapshots" else 32)
    assert again.committed == 8
    assert again.parent_hash == again.blocks[7].seq_hash


@pytest.mark.parametrize("kind", ["window", "snapshots", "conv"])
@pytest.mark.parametrize("option", list(OPTIONS))
def test_refuse_options_names_the_option_and_the_hold(kind, option):
    """What carries ONE paged cache under ONE table refuses, at
    construction and by name, a model whose sequences hold more; a model
    with one pool takes every option."""
    kw, word = OPTIONS[option]
    mirror = object() if option == "mirror" else None
    with pytest.raises(ValueError, match=f"{word}.*{WORDS[kind]}"):
        KvManager(_cfg(kind, **kw), snapshot_rows=64, mirror=mirror)
    assert KvManager(_cfg("one", **kw), mirror=mirror).beyond == ""


@pytest.mark.parametrize("kind", ["window", "snapshots", "conv"])
@pytest.mark.parametrize("call", CALLS)
def test_refuse_transfer_names_the_call_and_the_hold(kind, call):
    kv = KvManager(_cfg(kind), snapshot_rows=64)
    with pytest.raises(ValueError) as e:
        kv.refuse_transfer(call)
    assert str(e.value).startswith(call) and WORDS[kind] in str(e.value)
    assert "per-sequence state" in str(e.value)
    _kv("one").refuse_transfer(call)


def test_a_state_written_at_block_ends_has_a_row_a_block():
    """A conv layer's state (LFM2): the snapshot pool is the identity,
    every committed block counts a snapshot, nothing cuts a hit and no
    segment names a row."""
    kv = KvManager(_cfg("conv"), snapshot_rows=64)
    tokens = _prompt(10, 41)
    _live(kv, tokens)
    assert kv.stats["state_snapshots"] == 10
    hold, history, _upload = kv.reserve(tokens, None)
    assert history == 40 and hold.snap_points == ()
    assert kv.snap_rows([(hold, 40)]) is None
    assert kv.restore_from(hold) == hold.blocks[9].idx
    assert kv.restore_from(hold) == -1 and kv.snapshots._pins == {}


def test_the_module_needs_no_device():
    """The arrows point one way: importing the manager pulls in neither
    JAX nor the scheduler nor a model's forward."""
    code = ("import sys, dynamo_tpu.engine.kv_manager; "
            "bad = [m for m in ('jax', 'dynamo_tpu.engine.engine', "
            "'dynamo_tpu.models.llama') if m in sys.modules]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True)
