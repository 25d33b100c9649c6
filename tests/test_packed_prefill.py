"""The packed prefill step: a tick's prefill batch holds only the slots
that prefill, padded to a power-of-two row bucket.

Scheduler level (host only): the ``PrefillChunk`` rows, their slots, the
padding rows, and the ``prefill_rows`` counter.  Engine level (a tiny
camformer stack on the CPU): per-slot pool state of the slots that do not
prefill is left bit-identical, a request that joins a busy batch streams
what it streams alone, and sync == overlap and tp=2 == tp=1 token for
token while the prefill batch is smaller than ``max_batch``.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model_def
from repro.models.module import init_params
from repro.serving import Request, RequestState, SamplingParams, ServeEngine
from repro.serving.kv_cache import TRASH_PAGE, PagedKVCache, pages_for
from repro.serving.scheduler import Scheduler, row_bucket

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# scheduler: the packed PrefillChunk


def _sched(max_batch, max_len=64, page=8, prefill_slice=8):
    per_seq = pages_for(max_len, page)
    kv = PagedKVCache(1 + max_batch * per_seq, page, max_batch, per_seq)
    return Scheduler(kv, max_batch=max_batch, max_len=max_len,
                     prefix_sharing=False, prefill_slice=prefill_slice)


def _submit(sched, plen, first, max_new=4):
    return sched.submit(Request(prompt=[first] + [7] * (plen - 1),
                                sampling=SamplingParams(max_new=max_new)))


@pytest.mark.parametrize("n,max_batch,rows", [
    (1, 8, 1), (2, 8, 2), (3, 8, 4), (5, 8, 8), (8, 8, 8), (5, 6, 6)])
def test_prefill_rows_are_the_bucket_of_the_prefilling_count(
        n, max_batch, rows):
    assert row_bucket(n, max_batch) == rows
    sched = _sched(max_batch, prefill_slice=16)
    for i in range(n):
        _submit(sched, 5 + i, 100 + i)
    pf = sched.plan_tick().prefill
    assert pf.tokens.shape == (rows, 16)
    for a in (pf.lens, pf.offsets, pf.scale_base, pf.sample_index,
              pf.slots):
        assert a.shape == (rows,) and a.dtype == np.int32
    assert pf.table.shape == (rows, sched.kv.max_pages_per_seq)
    assert list(pf.slots) == list(range(n)) + [max_batch] * (rows - n)
    assert list(pf.lens[:n]) == [5 + i for i in range(n)]
    # padding rows: nothing to compute, nothing to write
    assert np.all(pf.lens[n:] == 0) and np.all(pf.tokens[n:] == 0)
    assert np.all(pf.table[n:] == TRASH_PAGE)
    for j in range(n):  # real rows: their slot's chunk and page table
        assert pf.tokens[j, 0] == 100 + j
        assert np.array_equal(pf.table[j], sched.kv.table[j])
    assert [e.slot for e in pf.emit] == list(range(n))
    assert sched.prefill_rows == rows


def test_prefill_rows_follow_the_slots_still_prefilling():
    """Slots 1 and 3 take three chunks, the rest one: the later ticks
    pack those two slots, in slot order, into a 2-row batch, and
    ``prefill_rows`` adds up the buckets."""
    sched = _sched(8)
    for i, plen in enumerate((3, 20, 3, 20, 3)):
        _submit(sched, plen, 100 + i)
    rows, slots = [], []
    while True:
        pf = sched.plan_tick().prefill
        if pf is None:
            break
        rows.append(len(pf.slots))
        slots.append([int(s) for s in pf.slots])
    assert rows == [8, 2, 2]
    assert slots[0] == [0, 1, 2, 3, 4, 8, 8, 8]
    assert slots[1:] == [[1, 3], [1, 3]]
    assert sched.prefill_rows == 12
    assert sched.prefill_ticks == 3
    assert sched.prefill_tokens == 3 + 20 + 3 + 20 + 3


# ---------------------------------------------------------------------------
# engine: slot state, joins, sync == overlap


def _cam(**kw):
    return smoke_config("codeqwen1.5-7b").replace(attn_backend="camformer",
                                                  **kw)


def _engine(cfg, **kw):
    md = get_model_def(cfg)
    params = init_params(md.specs(cfg), jax.random.PRNGKey(0))
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    return ServeEngine(md, cfg, params, **kw)


def _slot_leaves(eng):
    """Every slot-indexed pool leaf, target and drafter, as numpy."""
    out = {}
    for tag, pools, axes in zip(("target", "draft"),
                                (eng.caches, eng.draft_caches),
                                eng._slot_axes):
        for name, ax in axes.items():
            if ax is not None:
                out[tag, name] = (np.asarray(pools[name]), ax)
    return out


@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("refill", [(0,), (2, 3, 4)])
def test_prefill_leaves_other_slots_state_bit_identical(refill, spec_k):
    """Five slots decode; the ``refill`` slots are cancelled and refilled,
    and a prefill-only tick runs (slots 2-4: a 4-row batch whose padding
    row's out-of-range slot would clip onto slot 4).  The k_scale (and,
    with spec_k, k_means) rows of the slots that did not prefill stay
    bit-identical, and every refilled slot keeps its new k_scale."""
    eng = _engine(_cam(), max_batch=5, mode="sync", spec_k=spec_k,
                  prefill_slice=8)
    for i in range(5):
        eng.submit(Request(prompt=[3 + i, 5, 8, 1, 9],
                           sampling=SamplingParams(max_new=30), rid=i))
    for _ in range(3):
        eng.step()
    assert all(r.state is RequestState.DECODING for r in eng.active)
    for slot in refill:
        eng.cancel(eng.active[slot].rid)
    before = _slot_leaves(eng)
    assert ("target", "k_scale") in before
    if spec_k:
        assert {("target", "k_means"), ("draft", "k_scale"),
                ("draft", "k_means")} <= set(before)
    for i in range(len(refill)):
        eng.submit(Request(prompt=[40 + i, 2, 6, 1, 7, 3],
                           sampling=SamplingParams(max_new=4), rid=10 + i))
    eng.schedule()
    rows0 = eng.prefill_rows
    eng.prefill()
    assert eng.prefill_rows - rows0 == row_bucket(len(refill), 5)
    after = _slot_leaves(eng)
    kept = [i for i in range(5) if i not in refill]
    for key, (old, ax) in before.items():
        new = after[key][0]
        assert np.array_equal(np.take(new, kept, axis=ax),
                              np.take(old, kept, axis=ax)), key
    # each refilled slot prefilled a new prompt: its running k_scale moved
    ks_old, ax = before["target", "k_scale"]
    ks_new = after["target", "k_scale"][0]
    for slot in refill:
        assert not np.array_equal(np.take(ks_new, slot, axis=ax),
                                  np.take(ks_old, slot, axis=ax)), slot


def _req(prompt, max_new, rid, temperature=0.0):
    return Request(prompt=prompt, rid=rid, sampling=SamplingParams(
        max_new=max_new, temperature=temperature, top_k=8))


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_join_into_busy_batch_streams_what_it_streams_alone(temperature):
    """A request that joins slot 2 of a busy batch (1-row prefills while
    slots 0, 1 and 3 decode) streams the same tokens as the same request
    served alone in slot 0: greedy, and sampled (its row samples with its
    slot's key and parameters)."""
    cfg = _cam()
    x = [17, 4, 9, 2, 11, 6, 3, 8, 5, 1, 12]
    alone = _engine(cfg, max_batch=4, mode="sync", prefill_slice=8)
    want = [o.token for o in alone.stream(_req(x, 8, 9, temperature))]
    busy = _engine(cfg, max_batch=4, mode="sync", prefill_slice=8)
    for i in range(4):
        busy.submit(_req([30 + i, 2, 7, 5], 20, i))
    for _ in range(3):
        busy.step()
    busy.cancel(2)
    rows0 = busy.prefill_rows
    busy.submit(_req(x, 8, 9, temperature))
    got = [o.token for o in busy.stream()
           if o.rid == 9 and o.token is not None]
    assert busy.prefill_rows - rows0 == 2  # two 1-row chunks (11 tokens)
    assert got == want


def _staggered(eng):
    """Seven sampled requests through three slots: after the first tick
    every prefill packs one or two joining slots."""
    for i in range(7):
        eng.submit(Request(
            prompt=[3 + i, 5, 8, 1, 9, 2, 4][:3 + i % 5], rid=i,
            sampling=SamplingParams(temperature=0.8, top_k=8,
                                    max_new=3 + 2 * i)))
    return [(o.rid, o.index, o.token) for o in eng.stream()]


def test_sync_equals_overlap_with_packed_prefill():
    cfg = _cam()
    got = {}
    for mode in ("sync", "overlap"):
        eng = _engine(cfg, max_batch=3, mode=mode, prefill_slice=8)
        got[mode] = _staggered(eng), eng.prefill_rows, eng.prefill_ticks
        assert eng.kv.free_pages == eng.kv.n_pages - 1
    assert got["sync"] == got["overlap"]
    rows, ticks = got["sync"][1:]
    assert rows < ticks * 3  # the joins prefilled in fewer rows


_TP = """
import jax
from repro.configs import smoke_config
from repro.models import get_model_def
from repro.models.module import init_params
from repro.serving import Request, SamplingParams, ServeEngine

cfg = smoke_config("codeqwen1.5-7b").replace(attn_backend="camformer")
md = get_model_def(cfg)
params = init_params(md.specs(cfg), jax.random.PRNGKey(0))

def run(tp):
    eng = ServeEngine(md, cfg, params, max_batch=5, max_len=64, page_size=8,
                      mode="overlap", prefill_slice=8, tp=tp)
    for i in range(8):
        eng.submit(Request(
            prompt=[3 + i, 5, 8, 1, 9, 2, 4, 6, 7][:3 + 2 * (i % 4)], rid=i,
            sampling=SamplingParams(temperature=0.8, top_k=8,
                                    max_new=3 + i)))
    outs = [(o.rid, o.index, o.token) for o in eng.stream()]
    return outs, eng.readbacks, eng.prefill_rows, eng.prefill_ticks

ref, got = run(1), run(2)
assert ref == got, (ref[1:], got[1:])
print("OK", len(ref[0]), "events,", ref[2], "rows in", ref[3], "ticks")
"""


def test_tp2_equals_tp1_with_packed_prefill():
    """At tp=2 the slot-indexed leaves shard on kv_heads, so the row
    gather and scatter stay local to each shard: the streams equal
    tp=1's (forced host devices, a subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", _TP], env=env, timeout=600,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout, r.stdout
