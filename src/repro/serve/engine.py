"""Continuous-batching serving engine over the ring-buffer KV caches.

The engine owns B fixed slots and runs three planned hot-path routes
(DESIGN.md §12):

* **ragged admission** — every admission wave packs the pending prompts
  into ONE ``qo_indptr``-style prefill batch (`core.index_plan.ragged_layout`
  + `models.transformer.prefill_ragged`); the packed KV rows are unpacked
  into the decode slots by a ``ragged_rows`` IndexPlan gather, so multiple
  prompts cost one forward instead of one forward each.
* **chunked prefill interleaved with decode** — with ``chunk`` set, long
  prompts are consumed ``chunk`` tokens per engine step
  (`models.transformer.prefill_chunk`) while the other slots keep
  decoding, so a long prompt never stalls live traffic.
* **per-slot positions** — decode threads a (B,) position vector through
  `models.transformer.decode_step`, so each slot masks its own ring
  length (admitted-late slots no longer attend rows beyond their prompt);
  on kernel backends the decode attention is the split-KV
  `kernels.flash.flash_decode` two-stage reduce.

Each admission wave, chunk wave, decode, KV unpack and host sample is a
span of `repro.utils.spans` carrying the rows or slots it covered.  For a
model with MoE layers, the admission, chunk-wave and decode programs also
return the counts of `models.moe.STATS` summed over the layers; the
engine pulls them with the logits and records them on the
``engine.admit``, ``engine.chunk_wave`` and ``engine.decode`` spans.

Static shapes throughout: one compiled ragged prefill per packed width,
one compiled chunk step, one compiled decode.  The seed's left-padded
bucket prefill survives as ``prefill_mode="bucket"`` — the measured
baseline in ``benchmarks/bench_serve.py`` and the only route for
architectures whose blocks cannot be segment-masked (recurrent state,
sliding windows: see `models.transformer.supports_ragged`).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import index_plan as ip
from repro.kernels import ops
from repro.models import moe
from repro.models import transformer as tf
from repro.utils.spans import span

Array = jax.Array


@dataclass
class Request:
    """One serving request: a prompt in, greedy-decoded tokens out."""

    rid: int  #: caller-chosen request id
    prompt: np.ndarray  # (S,) int32
    max_new: int = 32  #: tokens to emit (the prefill's first token counts)
    out: list = field(default_factory=list)  #: emitted token ids
    done: bool = False
    slot: int | None = None  #: engine slot while live (admission placement)


class Engine:
    """Slot-based continuous batching: admit into free slots, decode all
    live slots per step, reuse slots the moment a request finishes."""

    def __init__(self, cfg, params, *, batch_slots: int = 4, s_max: int = 256,
                 prompt_bucket: int = 64, prefill_mode: str | None = None,
                 chunk: int | None = None):
        """``prefill_mode`` is ``"ragged"`` (packed admission waves),
        ``"bucket"`` (the seed's one-row left-padded prefill) or ``None``
        to pick ragged whenever the architecture supports it.  ``chunk``
        (ragged mode only) caps the tokens prefilled per engine step:
        admission packs the first ``chunk`` prompt tokens, the remainder
        streams through `models.transformer.prefill_chunk` interleaved
        with decode."""
        self.cfg = cfg
        self.params = params
        self.b = batch_slots
        self.s_max = s_max
        self.bucket = prompt_bucket
        ragged_ok = tf.supports_ragged(cfg)
        if prefill_mode is None:
            prefill_mode = "ragged" if ragged_ok else "bucket"
        if prefill_mode not in ("ragged", "bucket"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if prefill_mode == "ragged" and not ragged_ok:
            raise ValueError(
                "prefill_mode='ragged' needs attention-only decoder blocks "
                "(models.transformer.supports_ragged)"
            )
        if chunk is not None and prefill_mode != "ragged":
            raise ValueError("chunked prefill rides the ragged route only")
        if chunk is not None and chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.mode = prefill_mode
        self.chunk = chunk
        self.cache = tf.init_cache(cfg, batch_slots, s_max)
        self.pos = np.zeros(batch_slots, np.int32)  # per-slot next ring row
        self.off = np.zeros(batch_slots, np.int64)  # per-slot prompt cursor
        self.chunking = np.zeros(batch_slots, bool)  # slots still prefilling
        self.live: list[Request | None] = [None] * batch_slots
        self.frontend = None
        self._finished: list[Request] = []  # done at admission, drained by step

        # named functions, so the programs are jit_decode, jit_prefill, ...
        def decode(p, tok, cache, pos):  # tok < 0: an idle slot
            return tf.decode_step(p, cfg, jnp.maximum(tok, 0), cache, pos, valid=tok >= 0)

        def prefill(p, toks):
            return tf.prefill(p, cfg, toks)

        def prefill_ragged(p, toks, seg, pos, last):
            return tf.prefill_ragged(p, cfg, toks, seg, pos, last)

        def prefill_chunk(p, toks, cache, pos, active, last):
            return tf.prefill_chunk(p, cfg, toks, cache, pos, active, last)

        self._decode = jax.jit(decode)
        self._prefill = jax.jit(prefill)
        self._prefill_ragged = jax.jit(prefill_ragged)
        self._prefill_chunk = jax.jit(prefill_chunk)

    # -- admission -----------------------------------------------------------

    def free_slots(self) -> list[int]:
        """Indices of currently unoccupied slots."""
        return [i for i, r in enumerate(self.live) if r is None]

    def admit(self, req: Request) -> int | None:
        """Admit one request; returns its slot, or ``None`` when full."""
        slots = self.admit_batch([req])
        return slots[0] if slots else None

    def admit_batch(self, reqs: list[Request]) -> list[int]:
        """Admit up to ``len(free slots)`` requests in one wave; in ragged
        mode the whole wave shares ONE packed prefill.  Returns the chosen
        slot per admitted request (prefix of ``reqs``).

        The wave is the span ``engine.admit``: ``rows_real`` prompt tokens
        prefilled in ``rows_computed`` rows (the packed width, or the
        bucket pads)."""
        free = self.free_slots()
        reqs = reqs[: len(free)]
        if not reqs:
            return []
        for r in reqs:
            if len(r.prompt) == 0:
                raise ValueError(f"request {r.rid}: empty prompt")
            if len(r.prompt) >= self.s_max:
                raise ValueError(
                    f"request {r.rid}: prompt ({len(r.prompt)}) does not fit "
                    f"the slot ring (s_max={self.s_max})"
                )
        slots = free[: len(reqs)]
        if self.mode == "ragged":
            heads = [
                min(len(r.prompt), self.chunk) if self.chunk else len(r.prompt)
                for r in reqs
            ]
            lay = ip.ragged_layout(tuple(heads), self.bucket)
            with span("engine.admit", n=len(reqs), rows_real=sum(heads),
                      rows_computed=lay.t_pad) as sp:
                self._admit_ragged(reqs, slots, heads, lay, sp)
        else:
            pads = [self._bucket_pad(r) for r in reqs]
            with span("engine.admit", n=len(reqs),
                      rows_real=sum(len(r.prompt) for r in reqs),
                      rows_computed=sum(pads)):
                for r, s, pad in zip(reqs, slots, pads):
                    self._admit_bucket(r, s, pad)
        return slots

    def _admit_ragged(self, reqs: list[Request], slots: list[int], heads, lay, sp) -> None:
        """Packed admission: prefill the head of every prompt (``heads``:
        all of it, or the first ``chunk`` tokens) in one ragged batch laid
        out as ``lay`` and gather the packed KV rows into the slots; the
        MoE counts land on the wave's span ``sp``."""
        toks = np.zeros((1, lay.t_pad), np.int32)
        for j, r in enumerate(reqs):
            toks[0, lay.indptr[j] : lay.indptr[j] + heads[j]] = r.prompt[: heads[j]]
        last = np.zeros((self.b,), np.int32)  # padded to B: stable jit shape
        last[: len(reqs)] = lay.last_ix
        logits, packed, counts = self._prefill_ragged(
            self.params,
            jnp.asarray(toks),
            jnp.asarray(lay.seg_ids),
            jnp.asarray(lay.positions),
            jnp.asarray(last),
        )
        with span("engine.write_ragged", n=len(reqs)):
            self.cache = _write_ragged(self.cache, packed, slots, lay, self.s_max)
        done = sum(h == len(r.prompt) for h, r in zip(heads, reqs))
        with span("engine.sample", n=done):
            lg = self._pull(logits, counts, sp)
            for j, (r, s) in enumerate(zip(reqs, slots)):
                r.slot = s
                self.live[s] = r
                self.pos[s] = heads[j]
                self.off[s] = heads[j]
                self.chunking[s] = heads[j] < len(r.prompt)
                if not self.chunking[s]:
                    self._emit(s, int(np.argmax(lg[j])))

    def _bucket_pad(self, req: Request) -> int:
        """The bucket width ``req``'s prompt is left-padded to."""
        pad = -(-len(req.prompt) // self.bucket) * self.bucket
        if pad > self.s_max:
            raise ValueError(
                f"request {req.rid}: prompt bucket ({pad}) exceeds s_max "
                f"({self.s_max})"
            )
        return pad

    def _admit_bucket(self, req: Request, slot: int, pad: int) -> None:
        """The seed route: one left-padded bucket prefill per request."""
        s = len(req.prompt)
        toks = np.zeros((1, pad), np.int32)
        toks[0, pad - s :] = req.prompt  # left-pad into the bucket
        logits, cache1 = self._prefill(self.params, jnp.asarray(toks))
        # copy the single-row cache into the slot (KV rows land at [0, pad))
        self.cache = _write_slot(self.cache, cache1, slot, self.s_max)
        req.slot = slot
        self.live[slot] = req
        self.pos[slot] = pad
        self.off[slot] = s
        self.chunking[slot] = False
        with span("engine.sample", n=1):
            self._emit(slot, int(np.argmax(np.asarray(logits)[0])))

    def _pull(self, logits, counts, sp) -> np.ndarray:
        """The logits on the host; for a model with MoE layers the counts
        come in the same transfer and are recorded on the span ``sp``."""
        if self.cfg.moe is None:
            return np.asarray(logits)
        lg, counts = jax.device_get((logits, counts))
        sp.attrs.update(zip(moe.STATS, map(int, counts)))
        return lg

    def _emit(self, slot: int, token: int) -> None:
        """Record one generated token for ``slot``; retire the request when
        it hits ``max_new`` or its ring is full."""
        r = self.live[slot]
        r.out.append(token)
        if len(r.out) >= r.max_new or self.pos[slot] >= self.s_max:
            r.done = True
            r.slot = None
            self.live[slot] = None
            self.chunking[slot] = False
            self._finished.append(r)

    # -- stepping ------------------------------------------------------------

    def _chunk_wave(self) -> None:
        """Advance every still-prefilling slot by one ``chunk``-token wave
        (inactive slots' caches pass through untouched); slots whose
        prompt completes emit their first token and start decoding."""
        slots = [i for i in range(self.b) if self.live[i] is not None and self.chunking[i]]
        if not slots:
            return
        c = self.chunk
        counts = {i: min(c, len(self.live[i].prompt) - int(self.off[i])) for i in slots}
        with span("engine.chunk_wave", slots=len(slots), rows_real=sum(counts.values()),
                  rows_computed=self.b * c) as sp:
            toks = np.zeros((self.b, c), np.int32)
            active = np.zeros((self.b,), bool)
            last = np.zeros((self.b,), np.int32)
            for i in slots:
                off, n = int(self.off[i]), counts[i]
                toks[i, :n] = self.live[i].prompt[off : off + n]
                active[i] = True
                last[i] = n - 1
            logits, self.cache, moe_counts = self._prefill_chunk(
                self.params,
                jnp.asarray(toks),
                self.cache,
                jnp.asarray(self.pos),
                jnp.asarray(active),
                jnp.asarray(last),
            )
            done = [i for i in slots if int(self.off[i]) + counts[i] == len(self.live[i].prompt)]
            with span("engine.sample", n=len(done)):
                lg = self._pull(logits, moe_counts, sp)
                for i in slots:
                    self.off[i] += counts[i]
                    self.pos[i] += counts[i]
                for i in done:
                    self.chunking[i] = False
                    self._emit(i, int(np.argmax(lg[i])))

    def step(self) -> list[Request]:
        """One engine step: a chunk wave for prefilling slots, then one
        decoded token for every live decoding slot (per-slot positions).
        Returns the requests that finished during this step."""
        self._chunk_wave()
        finished, self._finished = self._finished, []
        decode_ix = [
            i for i, r in enumerate(self.live)
            if r is not None and not self.chunking[i]
        ]
        if decode_ix:
            kv_rows = sum(min(int(self.pos[i]) + 1, self.s_max) for i in decode_ix)
            with span("engine.decode", slots=len(decode_ix), kv_rows=kv_rows) as sp:
                toks = np.full((self.b,), -1, np.int32)  # -1: an idle slot
                for i in decode_ix:
                    toks[i] = self.live[i].out[-1]
                logits, self.cache, counts = self._decode(
                    self.params, jnp.asarray(toks), self.cache, jnp.asarray(self.pos)
                )
                with span("engine.sample", n=len(decode_ix)):
                    lg = self._pull(logits, counts, sp)
                    for i in decode_ix:
                        self.pos[i] += 1
                        self._emit(i, int(np.argmax(lg[i])))
            finished.extend(self._finished)
            self._finished = []
        return finished

    def run(self, requests: list[Request]) -> list[Request]:
        """Serve ``requests`` to completion; returns them in completion
        order (no per-step re-scan of the request list)."""
        pending = deque(requests)
        done: list[Request] = []
        while pending or any(r is not None for r in self.live):
            n_free = len(self.free_slots())
            if pending and n_free:
                wave = [pending.popleft() for _ in range(min(n_free, len(pending)))]
                self.admit_batch(wave)
            done.extend(self.step())
        return done

    def reset(self) -> None:
        """Drop all slot state (cache contents, positions, live requests)
        while keeping the compiled steps — benchmarks replay traces on one
        engine instance so jit caches stay warm."""
        self.cache = tf.init_cache(self.cfg, self.b, self.s_max)
        self.pos[:] = 0
        self.off[:] = 0
        self.chunking[:] = False
        self.live = [None] * self.b
        self._finished = []


def _write_slot(cache, cache1, slot: int, s_max: int):
    """Copy a 1-row prefill cache into slot ``slot`` of the engine cache,
    padding KV sequence dims up to s_max."""

    def merge(dst, src):
        if isinstance(dst, dict):
            return {k: merge(dst[k], src[k]) for k in dst}
        if isinstance(dst, list):
            return [merge(a, b) for a, b in zip(dst, src)]
        # dst (count, B, ...), src (count, 1, ...)
        if dst.ndim >= 3 and src.shape[1] == 1:
            row = src[:, 0]
            target = dst.shape[:1] + dst.shape[2:]  # slot slice shape
            if row.shape != target:
                # KV ring buffers: prefill wrote fewer sequence rows; pad
                # the seq axis (-2) up to the engine's s_max
                pad = [(0, 0)] * row.ndim
                pad[-2] = (0, target[-2] - row.shape[-2])
                row = jnp.pad(row, pad)
            return dst.at[:, slot].set(row.astype(dst.dtype))
        return dst

    return merge(cache, cache1)


def _write_ragged(cache, packed, slots: list[int], lay, s_max: int):
    """Unpack a packed ragged-prefill cache into the engine slots.

    Every KV leaf of ``packed`` is (count, 1, Hkv, t_pad, D) with rows in
    packed order; the move into (count, B, Hkv, s_max, D) slot rows is ONE
    masked ``ragged_rows`` IndexPlan gather per leaf — sequence j's rows
    ``[indptr[j], indptr[j+1])`` land at slot rows ``[0, len_j)``, the -1
    sentinels past each length zero-fill the ring tail."""
    n = len(slots)
    s_eff = min(s_max, lay.t_pad)
    unp = lay.unpack_index(s_eff)  # (n, s_eff), -1 past each length
    slots_arr = np.asarray(slots, np.int32)

    def merge(dst, src):
        if isinstance(dst, dict):
            return {k: merge(dst[k], src[k]) for k in dst}
        if isinstance(dst, list):
            return [merge(a, b) for a, b in zip(dst, src)]
        count, _, hkv, t_pad, d = src.shape
        flat = src.reshape(count * hkv * t_pad, d)
        # packed row of (layer c, head h, token t) is (c*hkv + h)*t_pad + t
        base = (np.arange(count * hkv, dtype=np.int64) * t_pad).reshape(
            count, 1, hkv, 1
        )
        u4 = unp[None, :, None, :]  # (1, n, 1, s_eff)
        idx = np.where(u4 >= 0, base + u4, -1).astype(np.int32)
        plan = ip.plan_index_op(
            flat.shape, flat.dtype, idx.size, "ragged_rows", masked=True
        )
        rows = ops.apply_index_plan(flat, jnp.asarray(idx.reshape(-1)), plan)
        rows = rows.reshape(count, n, hkv, s_eff, d)
        return dst.at[:, slots_arr, :, :s_eff].set(rows.astype(dst.dtype))

    return merge(cache, packed)
