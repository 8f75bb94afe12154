"""Token-granularity continuous batching (Orca, Yu et al., OSDI 2022)
over the paged decode engine.

One worker thread per generate-enabled servable runs the generation
loop: start ONE decode tick, retire the tick before it (sample, finish
the rows that ended), take the first tokens of the prefills started a
loop ago, start the prefills of waiting sequences, repeat. The
load-bearing property is WHERE admission happens: between every tick
(token granularity), so a new request starts decoding the moment a batch
slot and KV blocks exist instead of waiting for the whole current batch
to drain.

One tick in flight. The device runs what it is handed in order, and a
call returns before the device has run it, so the loop hands over tick
N+1 before it waits for tick N's ids: the host's own work for tick N
(the wait, the copy down, sampling, finishing, admission) happens while
the chip runs tick N+1. What tick N+1 needs of tick N is each row's new
token, and for a greedy row that is the argmax the tick already took on
the device: `DecodeEngine.start_tick(..., after=)` selects it there. The
rest the host knows beforehand: a row that reaches `max_tokens` or the
context's end with tick N's token is left out of tick N+1; a row that
tick N ends on a stop id is found out one tick late, and its row of tick
N+1 is computed and discarded (`wasted_rows`). A prefill is started
behind the tick in flight and its logits are fetched a loop later, after
the next tick has been queued behind it, so an admission does not drain
the device either. The choice is made a tick at a time from the rows
themselves: a row at a temperature needs its logits on the host before
its next token exists, so while one is running (or the scheduler is
closing) every tick is retired before the next is composed, as ticks
always were before (`mode="serial"` of `dl4j_decode_ticks_total`).

Invariant per sequence: ``ctx`` is prompt + every sampled token that has
reached the host, and ``cached`` counts how many of ctx's K/V live in
the arena or will when the steps in flight have run. Prefill caches all
of ctx at once and samples token ``len(ctx)``; each tick feeds token
``cached`` at position ``cached`` (``ctx[cached]``, or the id still on
the device) and samples the next. Eviction (KV-block pressure) just
frees the blocks and sets ``cached = 0`` — on re-admission the sequence
re-prefills its whole ctx and continues, so a greedy sequence is
reproducible across evictions.

Batch composition per tick goes through the serving batcher's
`FlushEma` (per-bucket tick-wall-time EMAs): with `avail` live rows it
either pads up to the next decode bucket or runs the largest full
bucket now, whichever maximizes rows/s — the DynamicBatcher flush
policy generalized to the decode plane. A rotating offset keeps row
selection fair when only a sub-batch runs.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from ...telemetry.recorder import flight_recorder
from ...telemetry.runtime import span as _span
from ..batcher import FlushEma
from ..registry import ServingError
from .cache import OutOfBlocksError
from .engine import DecodeEngine

__all__ = ["GenerationScheduler", "GenerationError"]


class GenerationError(ServingError):
    """A generation request failed (bad arguments, scheduler closed, or
    a sequence could not hold its KV blocks)."""


class _Seq:
    __slots__ = ("sid", "ctx", "prompt_len", "max_tokens", "temperature",
                 "stop_ids", "rng", "blocks", "cached", "event", "result",
                 "error", "trace", "submitted_at", "enqueued_at",
                 "first_token_at", "epoch", "prefill")

    def __init__(self, sid, prompt, max_tokens, temperature, stop_ids, seed,
                 trace=None):
        self.sid = sid
        self.ctx: List[int] = list(prompt)
        self.prompt_len = len(prompt)
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.stop_ids = frozenset(stop_ids)
        self.rng = np.random.default_rng(sid if seed is None else seed)
        self.blocks: List[int] = []
        self.cached = 0                 # ctx tokens whose K/V are cached
        self.epoch = 0                  # times its blocks were given up
        self.prefill: Optional[_Prefill] = None     # started, not fetched
        self.event = threading.Event()
        self.result: Optional[Dict] = None
        self.error: Optional[Exception] = None
        self.trace = trace              # TraceContext, or None
        self.submitted_at = time.perf_counter()
        self.enqueued_at = self.submitted_at    # restarts on a re-queue
        self.first_token_at: Optional[float] = None

    @property
    def generated(self) -> List[int]:
        return self.ctx[self.prompt_len:]


class _Prefill:
    """A sequence's prefill in flight: what `start_prefill` returned, and
    what the admission already knows for the `dl4j/sched/admit` span that
    its fetch will write."""
    __slots__ = ("started", "reserve", "t_pop", "t0", "t1", "queue_wait")

    def __init__(self, started, reserve, t_pop, t0, queue_wait):
        self.started, self.reserve = started, reserve
        self.t_pop, self.queue_wait = t_pop, queue_wait
        self.t0, self.t1 = t0, time.perf_counter()     # around start_prefill


class _Tick:
    """A tick in flight: what `start_tick` returned, its rows (each with
    the epoch it had: a row whose sequence has given up its blocks since
    is discarded), each row's place by sequence id, and what the
    `dl4j/sched/tick` span of its retirement will say."""
    __slots__ = ("started", "rows", "where", "reserve", "t0", "t1",
                 "overlapped", "device_ids")

    def __init__(self, started, batch, reserve, t0, overlapped, device_ids):
        self.started, self.reserve = started, reserve
        self.t0, self.t1 = t0, time.perf_counter()     # around start_tick
        self.rows = [(s, s.epoch) for s in batch]
        self.where = {s.sid: i for i, s in enumerate(batch)}
        self.overlapped, self.device_ids = overlapped, device_ids


class GenerationScheduler:
    """Continuous-batching generation loop for one servable: waiting
    sequences are admitted between every tick."""

    def __init__(self, registry, name: str, *, block_len: int = 16,
                 num_blocks: Optional[int] = None,
                 kv_dtype: str = "fp32",
                 decode_buckets: Sequence[int] = (1, 2, 4, 8),
                 prompt_buckets: Optional[Sequence[int]] = None,
                 metrics=None, idle_wait_s: float = 0.02,
                 arm: str = "stable"):
        self.name = name
        # canary arm this scheduler serves: a "canary" scheduler
        # resolves the candidate version each tick (falling back to
        # stable after a rollback — the existing flush-on-version-change
        # path then restarts its running sequences on the stable version)
        self.arm = arm
        self.registry = registry
        self.engine = DecodeEngine(
            registry, name, block_len=block_len, num_blocks=num_blocks,
            kv_dtype=kv_dtype, decode_buckets=decode_buckets,
            prompt_buckets=prompt_buckets)
        self.pool = self.engine.new_pool(metrics)
        self._ema = FlushEma()
        self._idle_wait_s = idle_wait_s
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._waiting: deque = deque()
        self._running: List[_Seq] = []
        self._closed = False
        self._ids = itertools.count(1)
        self._rotate = 0
        self._version = None
        self._flight: Optional[_Tick] = None    # the tick not yet retired
        # when the host last saw the device's stream reach a step's end
        self._seen = 0.0
        self._evictions = self._prefills = self._ticks = 0
        self._tokens_c = self._admit_c = self._evict_c = self._ticks_c = None
        self._phase_h = self._host_h = self._queue_h = None
        self._first_h = self._rows_h = None
        if metrics is not None:
            self._tokens_c = metrics.counter(
                "dl4j_decode_tokens_total", "generated tokens",
                labels=("model",))
            self._admit_c = metrics.counter(
                "dl4j_decode_admissions_total",
                "sequences admitted to the decode batch", labels=("model",))
            self._evict_c = metrics.counter(
                "dl4j_decode_evictions_total",
                "sequences preempted for KV-block pressure",
                labels=("model",))
            self._ticks_c = metrics.counter(
                "dl4j_decode_ticks_total",
                "decode ticks by how they were started: overlapped (with "
                "the tick before still in flight) or serial",
                labels=("model", "mode"))
            self._phase_h = metrics.histogram(
                "dl4j_decode_phase_seconds",
                "wall seconds the scheduler thread spent on a compiled "
                "generation step: starting it and waiting for it, less "
                "what it did between the two with the step in flight",
                labels=("model", "phase"))
            self._host_h = metrics.histogram(
                "dl4j_decode_host_seconds",
                "wall seconds per scheduler-thread span; phase is the "
                "span name's last element (loop, idle, admit, tick, "
                "reserve, sample, tick.prepare, tick.dispatch, ...)",
                labels=("model", "phase"))
            self._queue_h = metrics.histogram(
                "dl4j_decode_queue_wait_seconds",
                "seconds from a sequence's (re-)enqueue to its admission",
                labels=("model",))
            self._first_h = metrics.histogram(
                "dl4j_decode_first_token_seconds",
                "seconds from submit to the first sampled token",
                labels=("model",))
            self._rows_h = metrics.histogram(
                "dl4j_decode_tick_rows", "live rows per decode tick",
                labels=("model",),
                buckets=tuple(float(b) for b in self.engine.decode_buckets))
        self._worker = threading.Thread(
            target=self._run,
            name=(f"dl4j-decode-sched-{name}" if arm == "stable"
                  else f"dl4j-decode-sched-{name}-{arm}"),
            daemon=True)
        self._worker.start()

    # -- client side -----------------------------------------------------
    def submit(self, prompt: Sequence[int], *, max_tokens: int = 16,
               temperature: float = 0.0, stop: Sequence[int] = (),
               seed: Optional[int] = None,
               timeout: Optional[float] = None, ctx=None) -> Dict:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise GenerationError("prompt must be non-empty")
        if max_tokens < 1:
            raise GenerationError("max_tokens must be >= 1")
        if len(prompt) >= self.engine.max_context:
            raise GenerationError(
                f"prompt of {len(prompt)} tokens leaves no room in the "
                f"context window ({self.engine.max_context})")
        with self._lock:
            if self._closed:
                raise GenerationError(f"{self.name}: scheduler is stopped")
            seq = _Seq(next(self._ids), prompt, int(max_tokens),
                       float(temperature), [int(t) for t in stop], seed,
                       trace=ctx)
            self._waiting.append(seq)
        self._wake.set()
        if not seq.event.wait(timeout):
            raise TimeoutError(f"{self.name}: generation timed out")
        if seq.error is not None:
            raise seq.error
        return seq.result

    def stop(self, drain: bool = True):
        with self._lock:
            self._closed = True
            if not drain:
                while self._waiting:
                    self._fail(self._waiting.popleft(),
                               GenerationError("scheduler stopped"))
        self._wake.set()
        self._worker.join()

    # -- worker side -----------------------------------------------------
    def _release(self, seq: _Seq):
        """Give up seq's blocks (and its state slot, which goes with the
        first) and whatever of it is in flight: a started prefill is
        dropped, a row of the tick in flight is discarded at the tick's
        retirement (`_Tick.rows` holds the epoch). The tick in flight may
        still read and write these blocks; that is safe, because whoever
        gets them next writes them through a step dispatched later, and
        the device runs its steps in the order they were dispatched."""
        self.pool.release(seq.blocks)
        seq.blocks = []
        seq.epoch += 1
        seq.prefill = None

    def _finish(self, seq: _Seq, reason: str):
        t0 = time.perf_counter()
        self._release(seq)
        seq.result = {"tokens": seq.generated, "finish_reason": reason,
                      "prompt_tokens": seq.prompt_len,
                      "generated_tokens": len(seq.generated)}
        if seq.trace is not None:
            # emitted BEFORE event.set(): the waiter wakes to a complete
            # trace (queue -> prefill -> ticks -> scatter) in the buffer
            seq.trace.emit("scatter", t0, time.perf_counter(),
                           model=self.name, finish_reason=reason,
                           generated=len(seq.generated))
        seq.event.set()

    def _fail(self, seq: _Seq, err: Exception):
        self._release(seq)
        seq.error = err
        seq.event.set()

    def _sample(self, seq: _Seq, logits: np.ndarray) -> int:
        """The next token from the row's logits `[V]`; a tick whose rows
        are all greedy hands in the token itself, a scalar: the engine
        took the argmax on the device."""
        if seq.temperature <= 0.0:
            return int(logits if logits.ndim == 0 else np.argmax(logits))
        z = logits.astype(np.float64) / seq.temperature
        z -= z.max()
        p = np.exp(z)
        return int(seq.rng.choice(len(p), p=p / p.sum()))

    def _append_sample(self, seq: _Seq, logits: np.ndarray) -> Optional[str]:
        """Sample the next token; the finish reason if that ends the
        sequence (the caller finishes it), else None."""
        tok = self._sample(seq, logits)
        if self._tokens_c is not None:
            self._tokens_c.inc(model=self.name)
        if tok in seq.stop_ids:
            return "stop"
        seq.ctx.append(tok)
        if len(seq.generated) >= seq.max_tokens:
            return "length"
        if len(seq.ctx) >= self.engine.max_context:
            return "context"
        return None

    def _observe(self, sp):
        """A closed span's seconds into dl4j_decode_host_seconds, under
        its name's last path element. The engine calls this too."""
        if self._host_h is not None:
            self._host_h.observe(sp.seconds, model=self.name,
                                 phase=sp.name.rpartition("/")[2])

    def _evict_one(self, keep: _Seq) -> bool:
        """Preempt the NEWEST running sequence other than `keep` back to
        the waiting queue (its blocks freed; it will re-prefill; what the
        steps in flight compute for it is discarded)."""
        victims = [s for s in self._running if s is not keep]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.sid)
        if victim.prefill is not None:
            # owed the token its prefill makes: taken first (this waits for
            # the device), or two sequences that do not fit side by side
            # would preempt each other for ever, neither getting anywhere
            self._join_one(victim)
            if not victim.blocks:       # it ended there: its blocks are free
                return True
        self._running.remove(victim)
        self._release(victim)
        victim.cached = 0
        victim.enqueued_at = time.perf_counter()   # re-queued: wait restarts
        with self._lock:
            self._waiting.appendleft(victim)
        self._evictions += 1
        if self._evict_c is not None:
            self._evict_c.inc(model=self.name)
        rec = flight_recorder()
        if rec.enabled:
            rec.record("decode/evict", model=self.name, sid=victim.sid,
                       kept_sid=keep.sid, ctx_len=len(victim.ctx),
                       free_blocks=self.pool.free_blocks())
        return True

    def _reserve(self, seq: _Seq, n_tokens: int) -> bool:
        """Grow seq's block table to cover `n_tokens` cache slots,
        evicting neighbours under pressure. False = impossible even
        alone (seq is failed)."""
        while True:
            need = self.engine.spec.blocks_for(n_tokens) - len(seq.blocks)
            if need <= 0:
                return True
            try:
                seq.blocks.extend(self.pool.alloc(need))
                return True
            except OutOfBlocksError as e:
                if not self._evict_one(seq):
                    if seq in self._running:
                        self._running.remove(seq)
                    self._fail(seq, GenerationError(str(e)))
                    return False

    def _flush_running(self):
        """Version swapped under us: preempt everything (sequences keep
        their ctx and re-prefill against the new weights)."""
        rec = flight_recorder()
        if rec.enabled and self._running:
            rec.record("decode/swap_flush", model=self.name,
                       preempted=len(self._running),
                       free_blocks=self.pool.free_blocks())
        for seq in list(self._running):
            self._running.remove(seq)
            self._release(seq)
            seq.cached = 0
            seq.enqueued_at = time.perf_counter()
            with self._lock:
                self._waiting.appendleft(seq)

    def _admit(self, v):
        """Start the prefill of every waiting sequence there is room for,
        behind the tick in flight; `_join` fetches their logits a loop
        later, when the next tick is queued behind them."""
        cap = self.engine.decode_buckets[-1]
        while True:
            with self._lock:
                if not self._waiting or len(self._running) >= cap:
                    return
                seq = self._waiting.popleft()
            t_pop = time.perf_counter()
            queue_wait = t_pop - seq.enqueued_at    # (re-)enqueue -> here
            if self._queue_h is not None:
                self._queue_h.observe(queue_wait, model=self.name)
            with _span("dl4j/sched/reserve").hold() as reserve:
                evicted = self._evictions
                ok = self._reserve(seq, len(seq.ctx))
                reserve.set(evicted=self._evictions - evicted)
            self._observe(reserve)
            if not ok:
                continue
            t0 = time.perf_counter()
            try:
                started = self.engine.start_prefill(
                    v, self.pool, seq.ctx, seq.blocks, observe=self._observe)
            except Exception as e:          # noqa: BLE001 - fail the seq
                self._fail(seq, e)
                continue
            seq.prefill = _Prefill(started, reserve, t_pop, t0, queue_wait)
            seq.cached = len(seq.ctx)
            self._running.append(seq)

    def _join(self):
        """The first token of every sequence whose prefill was started a
        loop ago: from here on it is a row of the ticks."""
        for seq in [s for s in self._running if s.prefill is not None]:
            self._join_one(seq)

    def _join_one(self, seq: _Seq):
        with _span("dl4j/sched/admit", sid=seq.sid,
                   prompt_len=seq.prompt_len) as admit:
            pre, seq.prefill = seq.prefill, None
            self._first_token(seq, pre, admit)
        self._observe(admit)

    def _first_token(self, seq: _Seq, pre: _Prefill, admit):
        """Fetch the prefill's logits and sample the first token, inside
        the sequence's `dl4j/sched/admit` span (which also gets the spans
        that timed the prefill's start)."""
        admit.set(queue_wait_s=pre.queue_wait)
        if seq.trace is not None:
            seq.trace.emit("queue_wait", seq.enqueued_at, pre.t_pop,
                           model=self.name, sid=seq.sid,
                           ctx_len=len(seq.ctx))
        t_in = time.perf_counter()
        pre.reserve.write()
        try:
            logits = self.engine.finish_prefill(pre.started,
                                                observe=self._observe)
        except Exception as e:          # noqa: BLE001 - fail the seq
            self._running.remove(seq)
            self._fail(seq, e)
            return
        t1 = self._seen = time.perf_counter()
        if self._phase_h is not None:       # as a tick's: start and wait
            self._phase_h.observe(pre.t1 - pre.t0 + t1 - t_in,
                                  model=self.name, phase="prefill")
        # the ordinal the readers select by: the phase histogram's count
        # after this prefill
        self._prefills += 1
        admit.set(prefill=self._prefills)
        if seq.trace is not None:
            seq.trace.emit("prefill", pre.t0, t1, model=self.name,
                           tokens=len(seq.ctx))
        if self._admit_c is not None:
            self._admit_c.inc(model=self.name)
        rec = flight_recorder()
        if rec.enabled:
            # KV-pool pressure at the admission decision point
            rec.record("decode/admit", model=self.name, sid=seq.sid,
                       prompt_len=seq.prompt_len,
                       blocks=len(seq.blocks),
                       free_blocks=self.pool.free_blocks())
        with _span("dl4j/sched/sample") as sample:
            reason = self._append_sample(seq, logits)
            sample.set(finished=int(reason is not None))
        self._observe(sample)
        if seq.first_token_at is None:      # not a re-prefill after eviction
            seq.first_token_at = sample.t1 * 1e-9
            first = seq.first_token_at - seq.submitted_at
            admit.set(first_token_s=first)
            if self._first_h is not None:
                self._first_h.observe(first, model=self.name)
            if seq.trace is not None:
                seq.trace.emit("first_token", seq.submitted_at,
                               seq.first_token_at, model=self.name,
                               sid=seq.sid)
        if reason is not None:
            self._running.remove(seq)
            self._finish(seq, reason)

    def _tick(self, v):
        """Start the next tick and retire the one in flight, in the order
        the running rows allow: all greedy, the next tick goes first, its
        tokens taken on the device from the one in flight; with a row at
        a temperature among them (its next token does not exist until the
        host has sampled it), or the scheduler closing, each tick is
        retired before another is composed."""
        overlap = not self._closed and all(
            s.temperature <= 0.0 for s in self._running)
        if not overlap:
            self._drain()
        prev = self._flight
        self._flight = self._start_tick(v, prev)
        if prev is not None:
            self._retire(prev)
        if not overlap:
            self._drain()

    def _drain(self):
        if self._flight is not None:
            prev, self._flight = self._flight, None
            self._retire(prev)

    def _ends_in(self, prev: _Tick, seq: _Seq) -> bool:
        """Whether `prev`, in flight, gives seq its last token for sure:
        `_append_sample`'s length and context ends, known beforehand (a
        stop id is not)."""
        return seq.sid in prev.where and (
            len(seq.ctx) + 1 - seq.prompt_len >= seq.max_tokens
            or len(seq.ctx) + 1 >= self.engine.max_context)

    def _start_tick(self, v, prev: Optional[_Tick]) -> Optional[_Tick]:
        """Compose and dispatch a tick of the rows that go on, `prev` (the
        tick in flight, if any) unseen: None where no row does."""
        live = [s for s in self._running if s.prefill is None
                and not (prev is not None and self._ends_in(prev, s))]
        if not live:
            return None
        # room for each row's next slot BEFORE composing the batch, so
        # an eviction never invalidates a row already in the padded step
        with _span("dl4j/sched/reserve").hold() as reserve:
            evicted = self._evictions
            for seq in live:
                if seq.blocks:              # _reserve may evict/fail rows
                    self._reserve(seq, seq.cached + 1)
            reserve.set(evicted=self._evictions - evicted)
        self._observe(reserve)
        live = [s for s in live if s.blocks]
        if not live:
            return None
        avail = len(live)
        rows = self._ema.pick_rows(avail, list(self.engine.decode_buckets),
                                   self.engine.decode_buckets[-1])
        order = live[self._rotate % avail:] + live[:self._rotate % avail]
        batch = order[:rows]
        self._rotate += rows
        bucket = self.engine.decode_bucket_for(len(batch))
        # a row of the tick in flight takes its token from that tick's
        # ids, on the device; any other row's is on the host
        where = prev.where if prev is not None else {}
        tokens = [-(where[s.sid] + 1) if s.sid in where else s.ctx[s.cached]
                  for s in batch]
        t0 = time.perf_counter()
        started = self.engine.start_tick(
            v, self.pool, tokens, [s.cached for s in batch],
            [s.blocks for s in batch], bucket, observe=self._observe,
            after=prev.started if prev is not None else None)
        for seq in batch:
            seq.cached += 1
        return _Tick(started, batch, reserve, t0, int(prev is not None),
                     sum(t < 0 for t in tokens))

    def _retire(self, t: _Tick):
        with _span("dl4j/sched/tick") as tick:
            self._retire_rows(t, tick)
        self._observe(tick)

    def _retire_rows(self, t: _Tick, tick):
        """Wait for a started tick, sample its rows and finish those that
        ended, inside the `dl4j/sched/tick` span that carries its ordinal
        (and gets the spans that timed its start)."""
        t_in = time.perf_counter()
        t.reserve.write()
        out = self.engine.finish_tick(
            t.started, observe=self._observe,
            greedy=all(s.temperature <= 0.0 for s, _ in t.rows))
        # what the flush policy is fed, the same for every bucket: from
        # the later of the tick's dispatch and the last end of a step the
        # host saw (the tick before it, or a prefill between them) to its
        # ids on the host. With a tick in flight that is the device's time
        # for this tick alone; retired at once, all of start and finish.
        now = time.perf_counter()
        dt, self._seen = now - max(t.t0, self._seen), now
        bucket = t.started.bucket
        self._ema.observe(bucket, dt)
        if self._phase_h is not None:
            # the scheduler thread's own time for the tick: starting it,
            # and the wait for it that was left when it was retired
            self._phase_h.observe(t.t1 - t.t0 + now - t_in, model=self.name,
                                  phase="decode")
            self._rows_h.observe(len(t.rows), model=self.name)
            self._ticks_c.inc(model=self.name,
                              mode="overlapped" if t.overlapped else "serial")
        # the ordinal the readers select by: the phase histogram's count
        # after this tick. A request's ticks are found by membership.
        self._ticks += 1
        with _span("dl4j/sched/sample") as sample:
            finished = wasted = 0
            for (seq, epoch), row in zip(t.rows, out):
                if seq.epoch != epoch:
                    # computed for a sequence that has ended since (a stop
                    # id, met one tick late) or was evicted: discarded
                    wasted += 1
                    continue
                reason = self._append_sample(seq, row)
                if reason is not None:
                    self._running.remove(seq)
                    self._finish(seq, reason)
                    finished += 1
            sample.set(finished=finished)
        self._observe(sample)
        tick.set(tick=self._ticks, rows=len(t.rows), bucket=bucket,
                 overlapped=t.overlapped, device_ids=t.device_ids,
                 wasted_rows=wasted,
                 requests=[s.trace.trace_id for s, _ in t.rows
                           if s.trace is not None])

    def _resolve_version(self):
        """The version this scheduler's arm serves this tick. Canary
        schedulers resolve through the registry's arm routing (which
        falls back to stable once the canary is promoted or rolled
        back); registries without the canary surface (ducks in tests)
        resolve the plain current version."""
        arm_version = getattr(self.registry, "arm_version", None)
        if arm_version is not None:
            return arm_version(self.name, self.arm)
        return self.registry.get(self.name)

    def _poll(self):
        """(idle, closed, waiting) under the lock. A tick in flight is
        work left: its rows may all have ended (a stop id)."""
        with self._lock:
            waiting = len(self._waiting)
            idle = not waiting and not self._running and self._flight is None
            return idle, self._closed, waiting

    def _run(self):
        while True:
            idle, closed, waiting = self._poll()
            if idle and not closed:
                # one span for the whole idle period; the wait happens on
                # the Event, never under self._lock, so submit()/stop()
                # can always get in to enqueue or close
                with _span("dl4j/sched/idle") as sp:
                    while idle and not closed:
                        self._wake.wait(self._idle_wait_s)
                        self._wake.clear()
                        idle, closed, waiting = self._poll()
                self._observe(sp)
            if idle:
                return
            with _span("dl4j/sched/loop", waiting=waiting,
                       running=len(self._running)) as loop:
                self._loop_once()
            self._observe(loop)

    def _loop_once(self):
        try:
            v = self._resolve_version()
            if self._version is not v:
                self._drain()
                self._flush_running()
                self._version = v
            self._tick(v)
            self._join()
            self._admit(v)
        except Exception as e:          # noqa: BLE001 - never die quietly
            self._flight = None
            for seq in list(self._running):
                self._running.remove(seq)
                self._fail(seq, e)
            with self._lock:
                while self._waiting:
                    self._fail(self._waiting.popleft(), e)
