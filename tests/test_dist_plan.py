"""Distributed plan engine: decompose -> local plan -> cache (DESIGN.md §10).

Two-process layout (same pattern as the launcher dry-run): the *planner*
tests are pure metadata and run in the normal tier-1 process; the
*execution* tests need an 8-device mesh, so a single launcher test re-runs
this file in a subprocess with ``--xla_force_host_platform_device_count=8``
and ``REPRO_DIST_CHILD=1`` (the recipe ``make test-dist`` runs directly).

Execution coverage (child process):
* sharded permute (local / all_to_all / replicate strategies), sharded
  interlace — bit-identical to the single-device path on 1x2 / 1x4 / 2x4
  meshes, fp32 + bf16, ragged dims and zero-size shards;
* halo-exchanged ``repeat(k)`` stencil programs — bit-identical for all
  four boundary modes, one ``ppermute`` pair per k-block in the jaxpr;
* expert-parallel ``moe_sort`` — bit-identical to dropless single-device
  sort dispatch, exactly one ``all_to_all`` per direction in the jaxpr;
* plan-cache identity across calls, and the Pallas-interpret dispatch mode
  for each workload (the local plans run the real kernels per shard).
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import dist_plan as dp
from repro.core import stencil as st

_CHILD = os.environ.get("REPRO_DIST_CHILD") == "1"
needs_mesh = pytest.mark.skipif(
    not _CHILD,
    reason="needs 8 forced host devices — run via make test-dist "
    "(the launcher test spawns the same thing as a subprocess)",
)

RNG = np.random.default_rng(7)
MESHES = [((1, 2), "b"), ((1, 4), "b"), ((2, 4), "b")]
DTYPES = [jnp.float32, jnp.bfloat16]

JACOBI = st.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4)


def rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def make_mesh(shape):
    return jax.make_mesh(
        shape, ("a", "b")[: len(shape)],
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
    )


def jaxpr_counts(fn, *args) -> dict:
    """Count collective primitive applications in the traced jaxpr (the
    ``prim[params]`` spelling — plain substrings would also match param
    names like ``all_gather_dimension``)."""
    s = str(jax.make_jaxpr(fn)(*args))
    return {
        "all_to_all": s.count("all_to_all["),
        "ppermute": s.count("ppermute["),
        "all_gather": s.count("all_gather["),
    }


# ---------------------------------------------------------------------------
# planner: strategy choice, cost model, cache (no devices needed)
# ---------------------------------------------------------------------------

MK4 = (("a", 1), ("b", 4))


def test_plan_local_when_sharding_rides_the_perm():
    p = dp.plan_dist_rearrange(MK4, P("b"), None, (8, 6, 12), jnp.float32, (1, 0, 2))
    assert p.strategy == "local" and p.bytes_on_wire == 0 and p.collectives == ()
    assert p.out_spec == (None, "b", None)  # sharding carried to position 1
    # the reused local plan is the per-shard shape
    assert p.local_key[0] == (2, 6, 12)


def test_plan_all_to_all_cost_model():
    p = dp.plan_dist_rearrange(
        MK4, P("b"), P(None, None, "b"), (8, 6, 12), jnp.float32, (1, 0, 2)
    )
    assert p.strategy == "all_to_all" and p.collectives == ("all_to_all",)
    gbytes = 8 * 6 * 12 * 4
    assert p.bytes_on_wire == gbytes * 3 // 4  # (P-1)/P of the array
    a, b, psz = p.detail
    assert (a, b, psz) == (0, 2, 4)
    assert p.local_key[0] == (8, 6, 3)  # re-sharded local shape
    assert "all_to_all" in p.describe()


def test_plan_replicate_fallback():
    # explicit fully-replicated output: no aligned all_to_all exists, the
    # planner falls back to all_gather (the "unshard this" request)
    p = dp.plan_dist_rearrange(
        MK4, P("b"), P(None, None, None), (8, 10, 12), jnp.float32, (1, 0, 2)
    )
    assert p.strategy == "replicate" and "all_gather" in p.collectives
    gbytes = 8 * 10 * 12 * 4
    assert p.bytes_on_wire == gbytes * 3  # every dev pulls 3 remote shards
    # cross-mesh-axis reshard has no aligned collective either
    p2 = dp.plan_dist_rearrange(
        (("a", 2), ("b", 4)), P("b"), P(None, None, "a"),
        (8, 10, 12), jnp.float32, (1, 0, 2),
    )
    assert p2.strategy == "replicate" and p2.detail[1] == ((2, "a"),)


def test_plan_rejects_unshardable():
    with pytest.raises(ValueError, match="not divisible"):
        dp.plan_dist_rearrange(MK4, P("b"), None, (6, 4), jnp.float32, (1, 0))
    with pytest.raises(ValueError, match="bad perm"):
        dp.plan_dist_rearrange(MK4, P("b"), None, (8, 4), jnp.float32, (0, 0))


def test_plan_shard_request_on_replicated_input_slices():
    # replicated in, sharded out: must NOT plan "local" (each shard would
    # return the full array and shard_map would mis-assemble) — it slices
    p = dp.plan_dist_rearrange(
        MK4, P(), P(None, "b"), (8, 6, 12), jnp.float32, (1, 0, 2)
    )
    assert p.strategy == "replicate" and p.bytes_on_wire == 0
    assert p.detail == ((), ((1, "b"),))  # no gathers, one slice
    # size-1 mesh axes shard nothing: any request over them stays local
    p2 = dp.plan_dist_rearrange(
        MK4, P("a"), P(None, "a"), (8, 6, 12), jnp.float32, (1, 0, 2)
    )
    assert p2.strategy == "local"


def test_plan_wire_bytes_count_replica_groups():
    # a collective over 'b' on an (a=2, b=4) mesh runs in BOTH a-groups:
    # total wire is 2x the per-group cost
    mk24 = (("a", 2), ("b", 4))
    gbytes = 8 * 6 * 12 * 4
    p1 = dp.plan_dist_rearrange(
        MK4, P("b"), P(None, None, "b"), (8, 6, 12), jnp.float32, (1, 0, 2)
    )
    p2 = dp.plan_dist_rearrange(
        mk24, P("b"), P(None, None, "b"), (8, 6, 12), jnp.float32, (1, 0, 2)
    )
    assert p1.bytes_on_wire == gbytes * 3 // 4
    assert p2.bytes_on_wire == 2 * p1.bytes_on_wire


def test_plan_multiaxis_sharding_stays_local_when_carried():
    # a dim sharded over BOTH mesh axes still permutes comm-free when the
    # output sharding rides the perm (shard_div divides by the product)
    p = dp.plan_dist_rearrange(
        (("a", 2), ("b", 4)), P(("a", "b")), None, (16, 6, 12), jnp.float32,
        (1, 0, 2),
    )
    assert p.strategy == "local" and p.bytes_on_wire == 0
    assert p.local_key[0] == (2, 6, 12)  # 16 / (2*4)


def test_plan_multiaxis_gather_order_minor_first():
    # replicate fallback on a multi-axis-sharded dim must all_gather the
    # MINOR axis first (major-first interleaves the blocks)
    p = dp.plan_dist_rearrange(
        (("a", 2), ("b", 4)), P(("a", "b")), P(None, None, None),
        (16, 6, 12), jnp.float32, (1, 0, 2),
    )
    assert p.strategy == "replicate"
    assert p.detail[0] == ((0, "b"), (0, "a"))  # minor 'b' gathered first


def test_plan_cache_identity():
    a = dp.plan_dist_rearrange(MK4, P("b"), None, (8, 6, 12), jnp.bfloat16, (2, 1, 0))
    b = dp.plan_dist_rearrange(MK4, P("b"), None, (8, 6, 12), jnp.bfloat16, (2, 1, 0))
    assert a is b
    # PartitionSpec and pre-normalized tuples hit the same key
    c = dp.plan_dist_rearrange(MK4, ("b", None, None), None, (8, 6, 12),
                               np.dtype("bfloat16"), (2, 1, 0))
    assert c is a
    before = dp.dist_plan_cache_info()["rearrange"].hits
    dp.plan_dist_rearrange(MK4, P("b"), None, (8, 6, 12), jnp.bfloat16, (2, 1, 0))
    assert dp.dist_plan_cache_info()["rearrange"].hits == before + 1


def test_plan_interlace_always_commfree():
    for spec in (P("b"), P(None, "b"), P()):
        p = dp.plan_dist_interlace(MK4, spec, (8, 16), jnp.float32, 3)
        assert p.strategy == "local" and p.bytes_on_wire == 0
        assert p.out_spec == p.in_spec


def test_plan_stencil_kblock_partition_and_wire():
    prog = JACOBI.repeat(12)
    p = dp.plan_dist_stencil(MK4, "b", (32, 16), jnp.float32, prog.stages, "zero")
    # Hl = 8 rows/shard, 12 radius-1 stages -> blocks of 8 + 4 stages
    assert p.strategy == "halo" and p.detail == ((8, 8), (4, 4))
    assert p.collectives == ("ppermute",) * 4  # one pair per k-block
    assert p.bytes_on_wire == (2 * 8 + 2 * 4) * 16 * 4 * 4
    a = dp.plan_dist_stencil(MK4, "b", (32, 16), jnp.float32, prog.stages, "zero")
    assert a is p


def test_plan_stencil_replicates_when_radius_exceeds_shard():
    big = st.fd_laplacian(3)  # radius 3 > Hl = 2
    p = dp.plan_dist_stencil(
        (("x", 8),), "x", (16, 16), jnp.float32, big.as_program().stages, "zero"
    )
    assert p.strategy == "replicate" and p.collectives == ("all_gather",)


def test_plan_moe_cost_model():
    p = dp.plan_dist_moe(MK4, "b", 32, 16, 8, 8, 2, jnp.float32)
    assert p.strategy == "ep" and p.collectives == ("all_to_all", "all_to_all")
    assert p.detail == (4, 2, 8, 2)  # (P, E_local, cap, k)
    slot_bytes = 8 * 8 * 16 * 4  # E*cap rows of D fp32 per source shard
    assert p.bytes_on_wire == 2 * slot_bytes * 3  # both directions, (P-1) remote
    # the reused local plans are the §4 blocked kernels
    assert p.local_key[0] == "gather_rows_blocked"
    assert p.local_key[1] == "gather_combine_blocked"
    with pytest.raises(ValueError, match="not divisible"):
        dp.plan_dist_moe(MK4, "b", 30, 16, 8, 8, 2, jnp.float32)


# ---------------------------------------------------------------------------
# execution: sharded permute / interlace (8-fake-device child)
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("mesh_shape,axis", MESHES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_shard_permute_local_matches_oracle(mesh_shape, axis, dtype):
    mesh = make_mesh(mesh_shape)
    x = rand((8, 37, 12), dtype)  # ragged middle dim
    xs = jax.device_put(x, NamedSharding(mesh, P(axis)))
    got = dp.shard_permute(xs, (1, 0, 2), mesh=mesh, in_spec=P(axis))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.transpose(x, (1, 0, 2)))
    )
    counts = jaxpr_counts(
        lambda v: dp.shard_permute(v, (1, 0, 2), mesh=mesh, in_spec=P(axis)), x
    )
    assert counts == {"all_to_all": 0, "ppermute": 0, "all_gather": 0}


@needs_mesh
@pytest.mark.parametrize("mesh_shape,axis", MESHES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_shard_permute_all_to_all_matches_oracle(mesh_shape, axis, dtype):
    mesh = make_mesh(mesh_shape)
    x = rand((8, 37, 12), dtype)
    xs = jax.device_put(x, NamedSharding(mesh, P(axis)))
    out_spec = P(None, None, axis)
    got = dp.shard_permute(xs, (1, 0, 2), mesh=mesh, in_spec=P(axis), out_spec=out_spec)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.transpose(x, (1, 0, 2)))
    )
    counts = jaxpr_counts(
        lambda v: dp.shard_permute(
            v, (1, 0, 2), mesh=mesh, in_spec=P(axis), out_spec=out_spec
        ),
        x,
    )
    assert counts["all_to_all"] == 1 and counts["all_gather"] == 0


@needs_mesh
def test_shard_permute_zero_size_shards():
    mesh = make_mesh((1, 4))
    x = jnp.zeros((8, 0, 4), jnp.float32)
    got = dp.shard_permute(
        x, (2, 1, 0), mesh=mesh, in_spec=P("b"), out_spec=P(None, None, "b")
    )
    assert got.shape == (4, 0, 8)


@needs_mesh
def test_shard_permute_replicate_fallback_matches_oracle():
    mesh = make_mesh((2, 4))
    x = rand((8, 10, 12), jnp.float32)
    # cross-axis reshard b -> a: replicate fallback (gather, permute, slice)
    got = dp.shard_permute(
        x, (1, 0, 2), mesh=mesh, in_spec=P("b"), out_spec=P(None, None, "a")
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.transpose(x, (1, 0, 2)))
    )
    counts = jaxpr_counts(
        lambda v: dp.shard_permute(
            v, (1, 0, 2), mesh=mesh, in_spec=P("b"), out_spec=P(None, None, "a")
        ),
        x,
    )
    assert counts["all_gather"] == 1 and counts["all_to_all"] == 0


@needs_mesh
def test_shard_permute_multiaxis_local_and_replicate_match_oracle():
    mesh = make_mesh((2, 4))
    x = jnp.asarray(np.arange(16 * 6 * 12).reshape(16, 6, 12), jnp.float32)
    want = np.asarray(jnp.transpose(x, (1, 0, 2)))
    xs = jax.device_put(x, NamedSharding(mesh, P(("a", "b"))))
    got = dp.shard_permute(xs, (1, 0, 2), mesh=mesh, in_spec=P(("a", "b")))
    np.testing.assert_array_equal(np.asarray(got), want)  # comm-free
    got = dp.shard_permute(
        xs, (1, 0, 2), mesh=mesh, in_spec=P(("a", "b")),
        out_spec=P(None, None, None),
    )
    np.testing.assert_array_equal(np.asarray(got), want)  # gather order


@needs_mesh
@pytest.mark.parametrize("spec", [P("b"), P(None, "b")])
def test_shard_interlace_matches_oracle(spec):
    from repro.kernels import ref

    mesh = make_mesh((1, 4))
    arrays = [rand((8, 16), jnp.float32) for _ in range(3)]
    sharded = [jax.device_put(a, NamedSharding(mesh, spec)) for a in arrays]
    got = dp.shard_interlace(sharded, mesh=mesh, spec=spec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.interlace(arrays)))
    counts = jaxpr_counts(
        lambda *vs: dp.shard_interlace(list(vs), mesh=mesh, spec=spec), *arrays
    )
    assert counts == {"all_to_all": 0, "ppermute": 0, "all_gather": 0}


@needs_mesh
def test_shard_permute_interpret_runs_plan_kernels(pallas_interpret):
    mesh = make_mesh((1, 4))
    x = rand((8, 37, 12), jnp.bfloat16)
    got = dp.shard_permute(
        x, (1, 0, 2), mesh=mesh, in_spec=P("b"), out_spec=P(None, None, "b")
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.transpose(x, (1, 0, 2)))
    )


# ---------------------------------------------------------------------------
# execution: halo-exchanged stencil programs
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("mesh_shape,axis", MESHES)
@pytest.mark.parametrize("boundary", st.BOUNDARIES)
def test_halo_stencil_bit_identical(mesh_shape, axis, boundary):
    mesh = make_mesh(mesh_shape)
    x = rand((32, 18), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(axis, None)))
    prog = JACOBI.repeat(6)
    want = prog(x, boundary=boundary)
    got = prog.shard(xs, mesh=mesh, axis=axis, boundary=boundary)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs_mesh
@pytest.mark.parametrize("dtype", DTYPES)
def test_halo_stencil_multiblock_ppermute_pairs(dtype):
    mesh = make_mesh((1, 4))
    x = rand((32, 18), dtype)
    prog = JACOBI.repeat(12)  # Hl=8 -> two k-blocks (8+4 stages)
    want = prog(x, boundary="zero")
    got = prog.shard(x, mesh=mesh, axis="b", boundary="zero")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    plan = dp.plan_dist_stencil(
        dp.mesh_key(mesh), "b", x.shape, x.dtype, prog.stages, "zero"
    )
    counts = jaxpr_counts(lambda v: prog.shard(v, mesh=mesh, axis="b"), x)
    assert counts["ppermute"] == len(plan.collectives) == 4  # one pair per block


@needs_mesh
def test_halo_stencil_mixed_radius_program():
    mesh = make_mesh((1, 4))
    x = rand((32, 18), jnp.float32)
    prog = st.box_blur(1).then(st.fd_laplacian(2)).repeat(2)  # radii 1,2,1,2
    want = prog(x, boundary="nearest")
    got = prog.shard(x, mesh=mesh, axis="b", boundary="nearest")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs_mesh
def test_halo_stencil_replicate_fallback_bit_identical():
    mesh = make_mesh((8,))
    x = rand((16, 18), jnp.float32)  # Hl=2 < radius 3
    prog = st.fd_laplacian(3).as_program()
    want = prog(x, boundary="reflect")
    got = prog.shard(x, mesh=mesh, axis="x", boundary="reflect")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs_mesh
def test_halo_stencil_zero_size():
    mesh = make_mesh((1, 4))
    x = jnp.zeros((32, 0), jnp.float32)
    assert JACOBI.repeat(2).shard(x, mesh=mesh, axis="b").shape == (32, 0)


@needs_mesh
def test_halo_stencil_interpret_fused_kernels(pallas_interpret):
    mesh = make_mesh((1, 4))
    x = rand((32, 18), jnp.float32)
    prog = JACOBI.repeat(6)
    for boundary in st.BOUNDARIES:
        want = prog(x, boundary=boundary)
        got = prog.shard(x, mesh=mesh, axis="b", boundary=boundary)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# execution: expert-parallel MoE
# ---------------------------------------------------------------------------


def _moe_setup():
    from repro import configs
    from repro.models import moe

    cfg = configs.get_config("deepseek-moe-16b-smoke")
    p = moe.moe_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(
        jax.random.PRNGKey(4), (2, 16, cfg.d_model), jnp.float32
    ).astype(cfg.np_dtype)
    return moe, cfg, p, x


@needs_mesh
@pytest.mark.parametrize("mesh_shape,axis", MESHES)
def test_moe_ep_bit_identical_to_dropless_sort(mesh_shape, axis):
    moe, cfg, p, x = _moe_setup()
    mesh = make_mesh(mesh_shape)
    psz = int(mesh.shape[axis])
    t = x.shape[0] * x.shape[1]
    want, aux_want = moe.moe_sort(p, cfg, x, capacity=t)  # dropless
    got, aux_got = moe.moe_sort_ep(p, cfg, x, mesh=mesh, axis=axis, capacity=t // psz)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.allclose(float(aux_want), float(aux_got), rtol=1e-5)


@needs_mesh
def test_moe_ep_one_all_to_all_per_direction():
    moe, cfg, p, x = _moe_setup()
    mesh = make_mesh((1, 4))
    counts = jaxpr_counts(
        lambda v: moe.moe_sort_ep(p, cfg, v, mesh=mesh, axis="b", capacity=8)[0], x
    )
    # dispatch out + combine return: exactly one all_to_all each way, and
    # no gathered-intermediate materialization (no all_gather)
    assert counts["all_to_all"] == 2 and counts["all_gather"] == 0
    plan = dp.plan_dist_moe(
        dp.mesh_key(mesh), "b", 32, cfg.d_model, cfg.moe.n_experts, 8,
        cfg.moe.top_k, x.dtype,
    )
    assert counts["all_to_all"] == len(plan.collectives)


@needs_mesh
def test_moe_ep_plan_cache_hits_across_calls():
    moe, cfg, p, x = _moe_setup()
    mesh = make_mesh((1, 4))
    moe.moe_sort_ep(p, cfg, x, mesh=mesh, axis="b", capacity=8)
    before = dp.dist_plan_cache_info()["moe"].hits
    moe.moe_sort_ep(p, cfg, x, mesh=mesh, axis="b", capacity=8)
    assert dp.dist_plan_cache_info()["moe"].hits > before


@needs_mesh
def test_moe_ep_interpret_blocked_kernels(pallas_interpret):
    moe, cfg, p, x = _moe_setup()
    mesh = make_mesh((1, 4))
    want, _ = moe.moe_sort(p, cfg, x, capacity=32)
    got, _ = moe.moe_sort_ep(p, cfg, x, mesh=mesh, axis="b", capacity=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs_mesh
@pytest.mark.parametrize("dtype", DTYPES)
def test_tuned_dist_plans_bit_identical(monkeypatch, dtype):
    """§11 autotuning on the dist engine: the tuner may swap strategies
    (all_to_all vs replicate, halo vs replicate), but every strategy is
    movement-only, so tuned execution stays bit-identical to untuned."""
    monkeypatch.setenv("REPRO_TUNE", "off")
    mesh = make_mesh((1, 4))
    x = rand((8, 37, 12), dtype)
    xs = jax.device_put(x, NamedSharding(mesh, P("b")))
    out_spec = P(None, None, "b")
    want = dp.shard_permute(
        xs, (1, 0, 2), mesh=mesh, in_spec=P("b"), out_spec=out_spec
    )
    got = dp.shard_permute(
        xs, (1, 0, 2), mesh=mesh, in_spec=P("b"), out_spec=out_spec, tuned=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    g = rand((32, 18), dtype)
    gs = jax.device_put(g, NamedSharding(mesh, P("b", None)))
    prog = JACOBI.repeat(6)
    want_s = prog(g, boundary="zero")
    got_s = dp.shard_stencil(
        prog, gs, mesh=mesh, axis="b", boundary="zero", tuned=True
    )
    tuned_plan = dp.plan_dist_stencil(
        dp.mesh_key(mesh), "b", g.shape, g.dtype, prog.stages, "zero", tuned=True
    )
    assert tuned_plan.strategy in ("halo", "replicate")
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


@needs_mesh
def test_blockwise_train_loss_matches_monolithic_on_mesh():
    """Blockwise-parallel training blocks (DESIGN.md §13) under the 2x4
    data/model mesh: the q/seq-chunked model's loss and grads match the
    monolithic model's with a data-sharded batch — chunking composes with
    SPMD sharding (chunks slice the sequence axis, which stays
    replicated)."""
    from repro import configs
    from repro.models import transformer as tf

    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    cfg = configs.get_config("qwen2-7b-smoke").with_(
        dtype="float32", n_layers=2
    )
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    sh = NamedSharding(mesh, P("data", None))
    tok = jax.device_put(jax.random.randint(k1, (8, 64), 0, cfg.vocab), sh)
    lab = jax.device_put(jax.random.randint(k2, (8, 64), 0, cfg.vocab), sh)

    def lossg(c):
        return jax.value_and_grad(lambda p: tf.loss_fn(p, c, tok, lab))(params)

    with jax.sharding.set_mesh(mesh):
        l_mono, g_mono = lossg(cfg)
        l_bw, g_bw = lossg(
            cfg.with_(blockwise=True, blockwise_chunk=32,
                      remat_policy="dots_saveable")
        )
    assert float(l_mono) == float(l_bw)
    maxdiff = max(
        float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(g_mono), jax.tree.leaves(g_bw))
    )
    assert maxdiff < 1e-6


# ---------------------------------------------------------------------------
# the launcher: run the whole file on 8 forced host devices
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(_CHILD, reason="already inside the 8-device child")
def test_dist_suite_on_8_fake_devices():
    """Re-run this module in a subprocess with 8 forced host devices (the
    ``make test-dist`` configuration) so every execution test above runs."""
    from repro.launch.mesh import fake_device_env

    env = {
        **os.environ,
        **fake_device_env(8),
        "JAX_PLATFORMS": "cpu",
        "REPRO_DIST_CHILD": "1",
        "PYTHONPATH": "src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1500,
    )
    assert r.returncode == 0, (r.stdout[-4000:] + "\n" + r.stderr[-2000:])
