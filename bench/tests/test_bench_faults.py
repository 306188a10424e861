"""A run with the timed path broken underneath comes out not correct:
an answer altered where it is produced."""

import jax.numpy as jnp


def test_sound_library_run_is_correct(drive, tiny_lib):
    run = drive("lib-hbm.rearrange", traffic=tiny_lib, seconds=0.3)
    assert run.correct and run.attempted >= len(tiny_lib["ops"])
    assert all(v == 0 for v, _ in run.checks.values())


def test_altered_library_answer_is_caught(drive, tiny_lib, monkeypatch):
    from repro.kernels import ops

    real = ops.permute
    monkeypatch.setattr(ops, "permute", lambda x, perm, **kw: real(x, perm, **kw).at[
        (0,) * x.ndim].add(jnp.asarray(1, x.dtype)))
    run = drive("lib-hbm.rearrange", traffic=tiny_lib, seconds=0.3)
    assert not run.correct
    bad = [k for k, (v, lim) in run.checks.items() if v > lim]
    assert bad and all(k.startswith("permute") for k in bad)


def test_altered_served_token_is_caught(drive, tiny_chat, monkeypatch):
    from repro.serve.engine import Engine

    cfg, traffic = tiny_chat
    sound = drive("qwen2-7b-8l.chat", config=cfg, traffic=traffic)
    assert sound.correct, sound.checks
    real = Engine._emit
    vocab = cfg["vocab_size"]
    monkeypatch.setattr(Engine, "_emit",
                        lambda self, slot, token: real(self, slot, (token + 1) % vocab))
    run = drive("qwen2-7b-8l.chat", config=cfg, traffic=traffic)
    assert not run.correct
    assert run.checks["served_logit_gap"][0] > run.checks["served_logit_gap"][1]
