"""The decode loop's one-deep pipeline against the same engine made to collect
after every dispatch: the cases every served family goes through
(``test_serving.py``, ``test_sala_serving.py``, ``test_nemotron_serving.py``,
``test_jamba_serving.py``, ``test_serving_sharded.py``), each a case of one
parametrised test there.

A family's file hands ``run`` a ``make_engine(temperature=0.0, **serve)``
and four prompts; a case serves the same requests twice on ONE engine (its
programs compile once; an idle engine is a fresh one) - as the loop runs,
with a step unread between two ``step()`` calls, and with ``collect()``
after every ``step()``, the order the loop had before it was pipelined -
and holds the ids of the first to those of the second, request by request.
"""

from __future__ import annotations

from gpt_2_distributed_tpu.serving.engine import RequestHandle

NEW = (14, 9, 17, 11)


def serve(eng, prompts, new=NEW, *, pipelined=True, after_step=None):
    """Submit, run to idle, return the handles; with ``pipelined`` false the
    engine collects after every step. Request i samples with key 100 + i."""
    streamed = {}
    handles = [
        eng.submit(p, n, rng=100 + i,
                   on_token=lambda req, t: streamed.setdefault(req.id, []).append(t))
        for i, (p, n) in enumerate(zip(prompts, new))]
    steps = 0
    while eng.has_work():
        eng.step()
        if not pipelined:
            eng.collect()
        if after_step is not None:
            after_step(handles)
        steps += 1
        assert steps < 5000, "the engine does not drain"
    assert all(h.done for h in handles)
    # every token went through the stream once, in order
    assert [streamed.get(h.id, []) for h in handles] == [h.generated for h in handles]
    assert eng.collect() == 0 and not eng.has_work()   # nothing is left unread
    return handles


def both_ways(eng, prompts, new=NEW, **kw):
    """(pipelined, collecting) handles of the same requests on ``eng``, the
    counters of each run, and the ids held equal."""
    def counted(pipelined):
        before = dict(eng.stats)
        handles = serve(eng, prompts, new, pipelined=pipelined, **kw)
        return handles, {k: eng.stats[k] - before[k] for k in before}

    lagged, lagged_stats = counted(True)
    plain, plain_stats = counted(False)
    assert [h.generated for h in lagged] == [h.generated for h in plain]
    assert [h.finish_reason for h in lagged] == [h.finish_reason for h in plain]
    assert plain_stats["decode_overlapped"] == 0
    assert 0 < lagged_stats["decode_overlapped"] <= lagged_stats["decode_steps"]
    assert lagged_stats["tokens_out"] == plain_stats["tokens_out"]
    assert eng._decode_fn._cache_size() == 1 and eng._feed_fn._cache_size() == 1
    return lagged, lagged_stats, plain_stats


def greedy(make_engine, prompts, **_):
    eng = make_engine()
    lagged, stats, plain = both_ways(eng, prompts)
    assert [len(h.generated) for h in lagged] == list(NEW)
    assert all(h.finish_reason == "length" for h in lagged)
    # no eos: no step is wasted, the two orders advance the same rows
    assert stats["decode_rows"] == plain["decode_rows"]
    return [h.generated for h in lagged]


def sampled(make_engine, prompts, **_):
    eng = make_engine(temperature=0.8)
    lagged, _, _ = both_ways(eng, prompts)
    assert len({tuple(h.generated[:6]) for h in lagged}) == len(lagged)
    return [h.generated for h in lagged]


def freed_slot(make_engine, prompts, **_):
    """Two slots, three requests: the first ends by length while the second
    decodes on. Its slot is free from the dispatch of its last step - before
    that token is read - and the step that reads the token has admitted the
    queued request into the slot."""
    eng = make_engine(max_batch=2)
    new = (4, 30, 6)
    seen = []

    def after_step(handles):
        seen.append((handles[0].done, eng.occupancy, eng.queue_depth,
                     len(handles[0].generated)))

    lagged, _, _ = both_ways(eng, prompts[:3], new, after_step=after_step)
    # its last token unread, its slot already free and the third still queued
    parting = [i for i, (done, occ, queued, n) in enumerate(seen)
               if not done and occ == 1 and queued == 1 and n == new[0] - 1]
    assert parting, seen
    done, occ, queued, n = seen[parting[0] + 1]
    assert done and (occ, queued, n) == (2, 0, new[0])
    assert [len(h.generated) for h in lagged] == list(new)
    return [h.generated for h in lagged]


def chunk_steps(make_engine, prompts, **_):
    """Chunked prefill beside a decoding row: a step that dispatches a chunk
    does not read the decode step in flight back first. The chunk goes out
    behind that step and the next decode step behind the chunk, so while a
    row decodes every decode step but the first is dispatched over an unread
    one; the chunk's row joins the step after its first token is read."""
    eng = make_engine(max_batch=2)
    new = (40, 6, 5)       # the first decodes on while the two others come and go
    lagged, stats, _ = both_ways(eng, prompts[:3], new)
    assert [len(h.generated) for h in lagged] == list(new)
    assert stats["prefill_dispatches"] > 2
    if eng.serve.prefill_chunk:
        assert stats["decode_overlapped"] == stats["decode_steps"] - 1
    return [h.generated for h in lagged]


def eos(make_engine, prompts, **_):
    """``eos_id`` set and hit in mid-stream: the row has run one step past
    it by the time the host reads it; that step's token is dropped, the
    stream ends with the EOS token and every block comes back."""
    new = (40, 30, 12, 36)
    streams = [h.generated
               for h in serve(make_engine(temperature=0.8), prompts, new)]
    longest = max(streams, key=len)
    # late in the longest stream: every prompt is prefilled by then, and the
    # loop runs a step ahead of its read-back
    k = max(i for i in range(2, len(longest) - 2) if longest[i] not in longest[:i])
    eng = make_engine(temperature=0.8, eos_id=longest[k])
    lagged, stats, plain = both_ways(eng, prompts, new)
    for h, full in zip(lagged, streams):
        cut = full.index(longest[k]) + 1 if longest[k] in full else len(full)
        assert h.generated == full[:cut]
        assert h.finish_reason == ("eos" if cut < len(full) or full[-1] == longest[k]
                                   else "length")
    assert any(h.finish_reason == "eos" for h in lagged)
    # the surplus step's row was dispatched, and counted
    assert stats["decode_rows"] > plain["decode_rows"]
    assert eng.allocator.available == eng.serve.num_blocks - 1
    return [h.generated for h in lagged]


def preempted(make_engine, prompts, squeeze, **_):
    """Watermark admission on a pool too small for the slots' growth: a
    victim takes its last sampled token and its chain head along, so the
    loop reads the unread step back before it preempts."""
    eng = make_engine(temperature=0.8, **squeeze)
    new = (40, 34, 20, 12)
    lagged, stats, plain = both_ways(eng, prompts, new)
    assert stats["preemptions"] > 0 and plain["preemptions"] > 0
    assert sum(h.preemptions for h in lagged) == stats["preemptions"]
    assert all(h.resumes == h.preemptions for h in lagged)
    assert eng.allocator.available == eng.serve.num_blocks - 1
    return [h.generated for h in lagged]


def migrated(make_engine, prompts, **_):
    """``extract_inflight`` with a step unread, at the moment a request has
    left its slot with its last token in that step: the step is read back
    first, so that request is done and the others cross the wire with every
    token sampled; no token is streamed twice."""
    dst = make_engine(temperature=0.8)
    new = (5, 30, 12, 21)
    want = [h.generated for h in serve(dst, prompts, new, pipelined=False)]

    src = make_engine(temperature=0.8)
    streamed = {}
    handles = [
        src.submit(p, n, rng=100 + i,
                   on_token=lambda req, t: streamed.setdefault(req.id, []).append(t))
        for i, (p, n) in enumerate(zip(prompts, new))]
    while sum(not h.done for h in handles) == src.occupancy + src.queue_depth:
        src.step()                    # until a request is neither slotted nor done
    assert src.has_work() and not handles[0].done
    assert len(handles[0].generated) == new[0] - 1
    moved = src.extract_inflight()
    assert handles[0].done and handles[0] not in moved   # its last token was read
    assert not src.has_work() and src.collect() == 0
    wired = [RequestHandle.from_wire(
        h.to_wire(), lambda req, t: streamed.setdefault(req.id, []).append(t))
        for h in moved]
    assert any(h.generated for h in wired)
    for h in wired:
        dst.adopt(h)
    dst.run_until_idle(max_steps=5000)
    by_id = {h.id: h.generated for h in [handles[0]] + wired}
    assert [by_id[h.id] for h in handles] == want
    assert [streamed[h.id] for h in handles] == want
    return want


CASES = {
    "greedy": greedy,
    "sampled": sampled,
    "freed-slot": freed_slot,
    "chunk-steps": chunk_steps,
    "eos": eos,
    "preempted": preempted,
    "migrated": migrated,
}


def run(case: str, make_engine, prompts, squeeze):
    """Case ``case`` for one family; returns the served ids."""
    return CASES[case](make_engine, prompts, squeeze=squeeze)
