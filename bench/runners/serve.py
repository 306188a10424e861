"""Open-loop serving of a dense decoder through ``repro.serve.engine.Engine``.

Requests arrive on a fixed schedule whether or not earlier ones have
finished, as independent users send them; the seed draws their token ids
and the weights.  Each is timed from
the moment it was due.  The host loop is the server: it admits waiting
requests into free slots (at most ``max_wave`` per admission wave) and
steps the engine while any slot is live.  A lead-in of the same traffic
runs before the window so that the slots are in steady state.

Set-up makes the weights on the device from the seed in one call, in the
dtype they are served in, and warms every program shape the traffic can
reach: ragged admission of 1 to ``max_wave`` requests at every packed
width, a chunk wave, and the decode step.

After the window the loop serves on until every request due in the
window has finished, reads the memory peak, frees the engine, and
compares a sample of those requests with the configuration's plain
reference: the one with the most served tokens, one whose prompt was
prefilled in chunks, and more drawn from the seed.
"""

from __future__ import annotations

import math
import random
import time
from collections import deque


def program_config(cfg_file: dict):
    """The program's model configuration, at the sizes the file states."""
    from repro import configs

    base = configs.get_config(cfg_file["program"])
    return base.with_(
        n_layers=cfg_file["num_hidden_layers"],
        d_model=cfg_file["hidden_size"],
        n_heads=cfg_file["num_attention_heads"],
        n_kv_heads=cfg_file["num_key_value_heads"],
        d_ff=cfg_file["intermediate_size"],
        vocab=cfg_file["vocab_size"],
        head_dim=cfg_file["hidden_size"] // cfg_file["num_attention_heads"],
        rope_theta=float(cfg_file["rope_theta"]),
        tie_embeddings=bool(cfg_file["tie_word_embeddings"]),
        dtype=cfg_file["torch_dtype"],
        qkv_bias=True,
    )


def program_params(w: dict, cfg):
    """The benchmark's weights in the program's parameter layout; refuses
    a layout that differs from the one the program builds itself."""
    import jax

    from bench.harness import Refused
    from repro.models import transformer as tf

    layer = {
        "attn": {"norm": {"scale": w["ln1"]}, "w_qkv": w["w_qkv"],
                 "b_qkv": w["b_qkv"], "w_o": w["w_o"]},
        "mlp": {"norm": {"scale": w["ln2"]}, "w_gate": w["w_gate"],
                "w_up": w["w_up"], "w_down": w["w_down"]},
    }
    params = {"embed": {"tok": w["embed"]}, "final_norm": {"scale": w["final_norm"]},
              "stages": [{"b0": layer}], "lm_head": w["lm_head"]}
    want = tf.abstract_params(cfg)
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))
    ):
        raise Refused("the program's parameter layout is not the dense decoder "
                      "layout this runner fills")
    return params


# -- traffic ------------------------------------------------------------------


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int):
    """``n`` lengths at evenly spaced quantiles of a clipped lognormal."""
    from statistics import NormalDist

    z = NormalDist()
    return [int(min(hi, max(lo, round(median * math.exp(sigma * z.inv_cdf((i + 0.5) / n))))))
            for i in range(n)]


def requests(traffic: dict, seconds: float) -> list:
    """The schedule, as three segments: the lead-in, the window and the
    drain.  Each holds ``rate * length`` requests ``(offset_s, prompt_len,
    output_len)``, offsets from the segment's start, with lengths and gaps
    between arrivals at evenly spaced quantiles of their distributions
    (the gaps scaled to fill the segment) in one fixed shuffled order.

    The order is the same for every seed, which changes only the token
    ids and the weights: with the order drawn from the seed, the tokens
    emitted inside the window differed by 13% between seeds (a long
    answer arriving late in the window emits most of its tokens after
    it), while a seed repeated its own number within 1%."""
    rng = random.Random(0)
    rate, p, o = traffic["rate_per_s"], traffic["prompt"], traffic["output"]
    segments = []
    for span in (traffic["lead_in_s"], seconds, traffic["drain_s"]):
        n = max(1, round(rate * span))
        prompts = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
        outputs = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
        gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        for xs in (prompts, outputs, gaps):
            rng.shuffle(xs)
        scale = span / sum(gaps)
        offsets = [scale * sum(gaps[:i]) for i in range(n)]
        segments.append(list(zip(offsets, prompts, outputs)))
    return segments


# -- the server loop ----------------------------------------------------------


class Server:
    """The host loop around one engine, with what it saw of each request."""

    def __init__(self, eng, run, max_wave: int):
        self.eng = eng
        self.run = run
        self.max_wave = max_wave
        self.reqs = []  # every request scheduled, in order of arrival
        self.due = {}  # rid -> host time it falls due
        self.pending = deque()  # arrived, not admitted
        self.next = 0  # index in ``reqs`` of the next request to arrive
        self.active = []  # admitted, not finished
        self.tokens = {}  # rid -> host times its tokens were seen
        self.admitted = {}  # rid -> time its admission wave started
        self.steps = []  # (kind, t0, t1, segments): what each call computed

    def schedule(self, reqs, offsets, t: float) -> None:
        """Requests falling due at ``t + offset``."""
        for r, off in zip(reqs, offsets):
            self.reqs.append(r)
            self.due[r.rid] = t + off

    def _stamp(self, now: float) -> None:
        still = []
        for r in self.active:
            ts = self.tokens[r.rid]
            ts.extend([now] * (len(r.out) - len(ts)))
            if not r.done:
                still.append(r)
        self.active = still

    def admit(self, pending: deque) -> None:
        eng = self.eng
        n = min(len(eng.free_slots()), len(pending), self.max_wave)
        wave = [pending.popleft() for _ in range(n)]
        segs = [(min(len(r.prompt), eng.chunk), 0, len(r.prompt) <= eng.chunk)
                for r in wave]
        t0 = time.perf_counter()
        with self.run.spans.span("admit", n=n):
            eng.admit_batch(wave)
        t1 = time.perf_counter()
        for r in wave:
            self.admitted[r.rid] = t0
            self.tokens[r.rid] = []
        self.active.extend(wave)
        self._stamp(t1)
        self.steps.append(("admit", t0, t1, segs))

    def step(self) -> None:
        eng = self.eng
        segs = []
        for i, r in enumerate(eng.live):
            if r is None:
                continue
            if eng.chunking[i]:
                n = min(eng.chunk, len(r.prompt) - int(eng.off[i]))
                segs.append((n, int(eng.pos[i]), int(eng.off[i]) + n == len(r.prompt)))
            else:
                segs.append((1, int(eng.pos[i]), True))
        t0 = time.perf_counter()
        with self.run.spans.span("step", n=len(segs)):
            eng.step()
        t1 = time.perf_counter()
        self._stamp(t1)
        self.steps.append(("step", t0, t1, segs))

    def serve(self, until) -> None:
        """Admit and step, taking arrivals as they fall due, until
        ``until(now)`` says stop or nothing is left to serve."""
        reqs = self.reqs
        while True:
            now = time.perf_counter()
            while self.next < len(reqs) and self.due[reqs[self.next].rid] <= now:
                self.pending.append(reqs[self.next])
                self.next += 1
            if until(now):
                return
            if self.pending and self.eng.free_slots():
                self.admit(self.pending)
            elif any(r is not None for r in self.eng.live):
                self.step()
            elif self.next < len(reqs):
                wait = self.due[reqs[self.next].rid] - now
                time.sleep(max(0.0, min(wait, 0.05)))
            else:
                return


def warm(eng, max_wave: int) -> None:
    """Run every program shape the traffic reaches once: ragged admission
    waves of 1..max_wave requests at every packed width they can fill, a
    chunk wave and decode steps."""
    import numpy as np

    from repro.serve.engine import Request

    c = eng.chunk
    rid = -1
    for n in range(1, max_wave + 1):
        for k in range(1, n + 1):  # packed width k * bucket, k <= n
            total = k * eng.bucket
            lens = [total // n + (j < total % n) for j in range(n)]
            reqs = []
            for ln in lens:
                reqs.append(Request(rid=rid, prompt=np.ones(min(ln, c), np.int32), max_new=2))
                rid -= 1
            eng.admit_batch(reqs)
            while any(r is not None for r in eng.live):
                eng.step()
            eng.reset()
    eng.admit_batch([Request(rid=rid, prompt=np.ones(2 * c, np.int32), max_new=2)])
    while any(r is not None for r in eng.live):
        eng.step()
    eng.reset()


def setup(run, log):
    """Weights from the seed, the engine, and every shape warmed:
    returns ``(reference module, weights, engine)``."""
    import jax

    from bench.harness import plugin
    from repro.serve.engine import Engine

    cfg_file = run.config
    ref = plugin("configs", run.cell.workload["config"] + ".ref", run.bench)
    cfg = program_config(cfg_file)
    kw = cfg_file["assumed"]
    t = time.perf_counter()
    weights = jax.block_until_ready(ref.make_weights(run.key(), cfg_file))
    n_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(weights))
    log(f"weights: {n_bytes / 1e9:.3f} GB in {time.perf_counter() - t:.1f} s")
    eng = Engine(cfg, program_params(weights, cfg), batch_slots=kw["batch_slots"],
                 s_max=kw["s_max"], prompt_bucket=kw["prompt_bucket"],
                 prefill_mode="ragged", chunk=kw["chunk"])
    t = time.perf_counter()
    warm(eng, run.traffic["max_wave"])
    log(f"warm-up: {time.perf_counter() - t:.1f} s")
    if run.require_chip:
        expect_kernels(eng)
    return ref, weights, eng


def expect_kernels(eng) -> None:
    """Refuse an engine whose decode or chunk-wave program holds no Pallas
    kernel: the kernels would not be what the window times."""
    import jax.numpy as jnp

    from bench import libops
    from bench.harness import Refused

    b, c = eng.b, eng.chunk
    z = jnp.zeros((b,), jnp.int32)
    programs = {
        "decode": eng._decode.lower(eng.params, z, eng.cache, z),
        "chunk wave": eng._prefill_chunk.lower(eng.params, jnp.zeros((b, c), jnp.int32),
                                               eng.cache, z, jnp.zeros((b,), bool), z),
    }
    for name, lowered in programs.items():
        if not libops.expects_kernel(lowered.compile().as_text()):
            raise Refused(f"the engine's {name} program holds no tpu_custom_call")


def measure(run, eng, traffic: dict, *, drain: bool = True):
    """Lead in, open the window, serve it, then drain: every request due
    in the window finishes, or ``drain_s`` passes.  Returns the server and
    the requests due in the window."""
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(run.seed)
    segments = []
    for seg in requests(traffic, run.seconds):
        base = sum(len(s[0]) for s in segments)
        segments.append((
            [Request(rid=base + i, max_new=ol,
                     prompt=rng.integers(0, run.config["vocab_size"], pl, dtype=np.int32))
             for i, (_, pl, ol) in enumerate(seg)],
            [off for off, _, _ in seg]))
    (lead, lead_off), (in_window, win_off), (tail, tail_off) = segments
    srv = Server(eng, run, traffic["max_wave"])
    t_start = time.perf_counter()
    srv.schedule(lead, lead_off, t_start)
    srv.serve(lambda now: now >= t_start + traffic["lead_in_s"])
    t0 = run.start_window()
    srv.schedule(in_window, win_off, t0)
    srv.schedule(tail, tail_off, t0 + run.seconds)
    trace_s = min(run.seconds, traffic.get("trace_seconds", run.seconds))
    with run.tracing():
        srv.serve(lambda now: now >= t0 + trace_s)
    srv.serve(lambda now: now >= t0 + run.seconds)
    run.end_window(t0, t0 + run.seconds)
    srv.backlog = len(srv.pending)
    if drain:
        deadline = t0 + run.seconds + traffic["drain_s"]
        srv.serve(lambda now: now >= deadline or all(r.done for r in in_window))
    run.records.update(steps=srv.steps, tokens=srv.tokens, admitted=srv.admitted,
                       due=srv.due, in_window=[r.rid for r in in_window])
    return srv, in_window


def run(run, log) -> None:
    """Set up, lead in, measure, drain, then check a sample."""
    from bench.harness import memory_peak
    from bench.stats import percentile

    ref, weights, eng = setup(run, log)
    srv, in_window = measure(run, eng, run.traffic)
    run.memory_peak_bytes = memory_peak(run)
    run.attempted = len(in_window)
    run.failed = sum(1 for r in in_window if not srv.tokens.get(r.rid))
    finished = [r for r in in_window if r.done]
    ttft = [(srv.tokens[r.rid][0] - srv.due[r.rid]) * 1e3 for r in in_window
            if srv.tokens.get(r.rid)]
    log(f"time to first token: median {percentile(ttft, 50)} ms, p90 {percentile(ttft, 90)} ms")
    log(f"window: {len(in_window)} requests due, {run.failed} without a token and "
        f"{len(finished)} finished by the drain's end; {len(srv.steps)} engine calls in all")
    chunk = eng.chunk
    del eng, srv
    worst, _ = check(run, ref, weights, finished, chunk, log)
    run.check("served_logit_gap", worst, run.config["limits"]["served_logit_gap"])


def sweep(run, rates, log) -> list:
    """Offered load against what the engine sustains: for each rate a lead
    in and a window, then the backlog left and the latencies seen."""
    from bench.harness import plugin

    _, weights, eng = setup(run, log)
    rows = []
    for rate in rates:
        eng.reset()
        run.records = {}
        traffic = dict(run.traffic, rate_per_s=rate, lead_in_s=5.0)
        srv, in_window = measure(run, eng, traffic, drain=False)
        row = {"rate_per_s": rate, "due": len(in_window), "backlog_at_close": srv.backlog,
               "unadmitted_at_close": sum(1 for r in in_window if r.rid not in srv.admitted)}
        for name in ("tokens_per_s", "ttft_p50_ms", "itl_p99_ms", "serve.queue_p50_ms"):
            row[name] = plugin("metrics", name, run.bench).read(run)
        log(f"  {row}")
        rows.append(row)
        # let the slots empty before the next rate
        srv.next = len(srv.reqs)
        srv.pending.clear()
        srv.serve(lambda now: False)
    return rows


def readings(run, seeds, log) -> list:
    """For each seed, a window at the cell's load, then the widest served
    logit gap of the program and of the control (the reference computed
    in 8 bits) on the same sample of finished requests, each with the
    verdict of the harness's comparison against the configuration's
    limit."""
    import jax

    from bench.harness import within

    limit = run.config["limits"]["served_logit_gap"]
    ref, weights, eng = setup(run, log)
    rows = []
    for seed in seeds:
        run.seed = seed
        run.records = {}
        eng.params = None
        del weights
        weights = jax.block_until_ready(ref.make_weights(run.key(), run.config))
        eng.params = program_params(weights, eng.cfg)
        eng.reset()
        srv, in_window = measure(run, eng, run.traffic)
        finished = [r for r in in_window if r.done]
        prog, ctrl = check(run, ref, weights, finished, eng.chunk, log, control=True)
        row = {"seed": seed, "check": "served_logit_gap", "program": prog, "control": ctrl,
               "limit": limit, "program_passes": within(prog, limit),
               "control_passes": within(ctrl, limit),
               "due": len(in_window), "unfinished": len(in_window) - len(finished)}
        log(f"  {row}")
        rows.append(row)
    return rows


def sample(finished, seed: int, tokens: int, chunk: int):
    """Finished requests to compare: the one with the most served tokens,
    one whose prompt is longer than ``chunk`` (prefilled in chunk waves)
    drawn from the seed, then more drawn from the seed until they hold
    ``tokens`` served tokens."""
    if not finished:
        return []
    rng = random.Random(seed)
    longest = max(finished, key=lambda r: (len(r.out), len(r.prompt)))
    out = [longest]
    chunked = [r for r in finished if len(r.prompt) > chunk]
    if len(longest.prompt) <= chunk and chunked:
        out.append(rng.choice(chunked))
    rest = [r for r in finished if r not in out]
    rng.shuffle(rest)
    n = sum(len(r.out) for r in out)
    for r in rest:
        if n >= tokens:
            break
        out.append(r)
        n += len(r.out)
    return out


def gaps(ref_logits, chosen):
    """How far each chosen token's reference logit lies below the
    reference's best, per position."""
    import numpy as np

    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]


def check(run, ref, weights, finished, chunk, log, control: bool = False):
    """The widest gap of a served token below the reference's best over a
    sample of finished requests; with ``control`` also the widest gap of
    the token the control puts first at the same positions.  Returns
    ``(program gap, control gap or None)``."""
    import numpy as np

    picks = sample(finished, run.seed, run.traffic["check_tokens"], chunk)
    worst, worst_ctrl, n_tok = 0.0, 0.0, 0
    t = time.perf_counter()
    for r in picks:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        first = len(r.prompt) - 1
        lg = ref.logits(weights, run.config, seq, first)
        worst = max(worst, float(gaps(lg, np.asarray(r.out)).max()))
        if control:
            low = ref.control_logits(weights, run.config, seq, first)
            worst_ctrl = max(worst_ctrl, float(gaps(lg, low.argmax(axis=-1)).max()))
        n_tok += len(r.out)
    log(f"check: {len(picks)} requests (prompts {[len(r.prompt) for r in picks]}), "
        f"{n_tok} served tokens against the reference in {time.perf_counter() - t:.1f} s")
    if not picks:
        return float("inf"), None
    return worst, (worst_ctrl if control else None)
