"""The seeded traffic generators are deterministic."""

import jax
import numpy as np

from bench import harness, libops


def test_serving_schedule_is_fixed():
    serve = harness.plugin("runners", "serve")
    traffic = harness.load_json("traffic", "chat")
    a = serve.requests(traffic, 20.0)
    assert a == serve.requests(traffic, 20.0)
    spans = (traffic["lead_in_s"], 20.0, traffic["drain_s"])
    for seg, span in zip(a, spans):
        assert len(seg) == round(traffic["rate_per_s"] * span)
        assert all(0 <= x[0] < span for x in seg)
        assert [x[0] for x in seg] == sorted(x[0] for x in seg)
    p = traffic["prompt"]
    assert all(p["min"] <= x[1] <= p["max"] for seg in a for x in seg)
    assert len({x[1] for x in a[1]}) > 1  # lengths vary within the window


def test_library_inputs_are_deterministic():
    key = jax.random.PRNGKey(3)
    x = libops.normal(key, (64, 128), np.float32)
    assert (np.asarray(x) == np.asarray(libops.normal(key, (64, 128), np.float32))).all()


def test_seed_key_takes_seeds_past_32_bits():
    run = harness.Run(cell=None, config={}, traffic={}, seed=2**33 + 1, seconds=1,
                      trace=False, t_process=0.0)
    other = harness.Run(cell=None, config={}, traffic={}, seed=1, seconds=1,
                        trace=False, t_process=0.0)
    assert not (np.asarray(run.key()) == np.asarray(other.key())).all()


def test_check_sample_holds_the_longest_and_a_chunked_prompt():
    from types import SimpleNamespace as R

    serve = harness.plugin("runners", "serve")
    reqs = [R(prompt=[0] * p, out=[0] * o) for p, o in
            [(100, 512), (40, 10), (600, 20), (900, 30), (50, 40), (60, 50)]]
    for seed in range(20):
        picks = serve.sample(reqs, seed, 300, chunk=512)
        assert picks[0] is reqs[0]
        assert any(len(r.prompt) > 512 for r in picks)
        assert picks == serve.sample(reqs, seed, 300, chunk=512)
    assert serve.sample([], 1, 300, chunk=512) == []
