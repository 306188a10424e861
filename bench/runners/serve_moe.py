"""Open-loop serving of a DeepSeekMoE decoder, as one chip's share of its
experts, through ``repro.serve.engine.Engine``.

The server loop, the traffic, the warm-up, the window and the check of
served tokens against the configuration's plain reference are the dense
runner's (``runners/serve.py``).  What differs is the model: a leading
dense layer, then MoE layers that route over all ``n_routed_experts`` of
the published router and compute the ``experts_held`` share of them
(``repro.models.moe.moe_held``).  A program without that layer cannot run
the cell: the run exits at once with no result.
"""

from __future__ import annotations

import time
from dataclasses import replace

from bench.harness import plugin

serve = plugin("runners", "serve")


def program_config(cfg_file: dict):
    """The program's model configuration at the sizes and the expert share
    the file states; raises ``ImportError`` for a program whose MoE layer
    cannot be told which experts it holds."""
    from repro import configs
    from repro.models.moe import moe_held  # noqa: F401 — the layer the cell needs

    from bench.harness import Refused

    if cfg_file["scoring_func"] != "softmax" or cfg_file["hidden_act"] != "silu":
        raise Refused("the program's router is a softmax over SwiGLU experts")
    held = cfg_file["experts_held"]
    if held["count"] != cfg_file["n_routed_experts"]:
        raise Refused("n_routed_experts counts the experts held here")
    base = configs.get_config(cfg_file["program"])
    dense = cfg_file["first_k_dense_replace"]
    d, heads = cfg_file["hidden_size"], cfg_file["num_attention_heads"]
    return base.with_(
        n_layers=cfg_file["num_hidden_layers"],
        explicit_plan=((("attn_dense",), dense),
                       (("attn_moe",), cfg_file["num_hidden_layers"] - dense)),
        d_model=d,
        n_heads=heads,
        n_kv_heads=cfg_file["num_key_value_heads"],
        head_dim=d // heads,
        d_ff=cfg_file["moe_intermediate_size"],
        d_ff_dense=cfg_file["intermediate_size"],
        vocab=cfg_file["vocab_size"],
        rope_theta=float(cfg_file["rope_theta"]),
        tie_embeddings=bool(cfg_file["tie_word_embeddings"]),
        dtype=cfg_file["torch_dtype"],
        qkv_bias=bool(cfg_file["attention_bias"]),
        moe=replace(base.moe, n_experts=held["of"], top_k=cfg_file["num_experts_per_tok"],
                    d_expert=cfg_file["moe_intermediate_size"],
                    n_shared=cfg_file["n_shared_experts"],
                    normalize_gates=bool(cfg_file["norm_topk_prob"]),
                    held=(held["first"], held["count"])),
    )


def program_params(w: dict, cfg):
    """The benchmark's weights in the program's parameter layout; refuses
    a layout that differs from the one the program builds itself."""
    import jax
    import jax.numpy as jnp

    from bench.harness import Refused
    from repro.models import transformer as tf

    def attn(sl):
        return {"norm": {"scale": w["ln1"][sl]}, "w_qkv": w["w_qkv"][sl], "w_o": w["w_o"][sl]}

    n_moe = w["w_router"].shape[0]
    dense = {"attn": attn(slice(0, 1)),
             "mlp": {"norm": {"scale": w["ln2"][:1]}, "w_gate": w["dense_gate"][None],
                     "w_up": w["dense_up"][None], "w_down": w["dense_down"][None]}}
    routed = {"attn": attn(slice(1, None)),
              "moe": {"norm": {"scale": w["ln2"][1:]}, "w_router": w["w_router"],
                      "w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"],
                      # ffn_only applies no norm of its own: this scale is unread
                      "shared": {"norm": {"scale": jnp.ones((n_moe, cfg.d_model), jnp.float32)},
                                 "w_gate": w["shared_gate"], "w_up": w["shared_up"],
                                 "w_down": w["shared_down"]}}}
    params = {"embed": {"tok": w["embed"]}, "final_norm": {"scale": w["final_norm"]},
              "stages": [{"b0": dense}, {"b0": routed}], "lm_head": w["lm_head"]}
    want = tf.abstract_params(cfg)
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))
    ):
        raise Refused("the program's parameter layout is not the DeepSeekMoE layout "
                      "this runner fills")
    return params


#: the grouped-matmul kernel of the held experts, by its name in the programs
GMM_KERNEL = "grouped_swiglu"


def expect_kernels(eng) -> None:
    """The dense runner's check, and the held experts' grouped kernel in
    the decode and chunk-wave programs."""
    import jax.numpy as jnp

    from bench.harness import Refused

    serve.expect_kernels(eng)
    b, c = eng.b, eng.chunk
    z = jnp.zeros((b,), jnp.int32)
    programs = {
        "decode": eng._decode.lower(eng.params, z, eng.cache, z),
        "chunk wave": eng._prefill_chunk.lower(eng.params, jnp.zeros((b, c), jnp.int32),
                                               eng.cache, z, jnp.zeros((b,), bool), z),
    }
    for name, lowered in programs.items():
        if f"%{GMM_KERNEL}" not in lowered.compile().as_text():
            raise Refused(f"the engine's {name} program holds no {GMM_KERNEL} kernel")


def setup(run, log):
    """Weights from the seed, the engine, and every shape warmed:
    returns ``(reference module, weights, engine)``."""
    import jax

    from repro.serve.engine import Engine

    cfg_file = run.config
    cfg = program_config(cfg_file)
    ref = plugin("configs", run.cell.workload["config"] + ".ref", run.bench)
    kw = cfg_file["assumed"]
    t = time.perf_counter()
    weights = jax.block_until_ready(ref.make_weights(run.key(), cfg_file))
    n_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(weights))
    log(f"weights: {n_bytes / 1e9:.3f} GB in {time.perf_counter() - t:.1f} s")
    eng = Engine(cfg, program_params(weights, cfg), batch_slots=kw["batch_slots"],
                 s_max=kw["s_max"], prompt_bucket=kw["prompt_bucket"],
                 prefill_mode="ragged", chunk=kw["chunk"])
    t = time.perf_counter()
    serve.warm(eng, run.traffic["max_wave"])
    log(f"warm-up: {time.perf_counter() - t:.1f} s")
    if run.require_chip:
        expect_kernels(eng)
    return ref, weights, eng


def run(run, log) -> None:
    """Set up, lead in, measure, drain, then check a sample."""
    from bench.harness import memory_peak
    from bench.stats import percentile

    ref, weights, eng = setup(run, log)
    srv, in_window = serve.measure(run, eng, run.traffic)
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
    worst, _ = serve.check(run, ref, weights, finished, chunk, log)
    run.check("served_logit_gap", worst, run.config["limits"]["served_logit_gap"])


def sweep(run, rates, log) -> list:
    """Offered load against what the engine sustains: for each rate a lead
    in and a window, then the backlog left and the latencies seen."""
    _, _, eng = setup(run, log)
    rows = []
    for rate in rates:
        eng.reset()
        run.records = {}
        traffic = dict(run.traffic, rate_per_s=rate, lead_in_s=5.0)
        srv, in_window = serve.measure(run, eng, traffic, drain=False)
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
    with 8-bit weight matmuls) on the same sample of finished requests,
    each with the verdict of the harness's comparison against the limit."""
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
        srv, in_window = serve.measure(run, eng, run.traffic)
        finished = [r for r in in_window if r.done]
        prog, ctrl = serve.check(run, ref, weights, finished, eng.chunk, log, control=True)
        row = {"seed": seed, "check": "served_logit_gap", "program": prog, "control": ctrl,
               "limit": limit, "program_passes": within(prog, limit),
               "control_passes": within(ctrl, limit),
               "due": len(in_window), "unfinished": len(in_window) - len(finished)}
        log(f"  {row}")
        rows.append(row)
    return rows
