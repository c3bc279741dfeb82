"""The home-sharded arena (repro.fed.arena): emulated-mesh routing
properties plus the subprocess A/B harness.

The routing helpers take the device index and the reduction as
arguments, so these tests emulate a D-device mesh *in-process*: each
"device" holds one (L, …) block of the padded arena, gathers are the sum
of the per-device ``take_rows`` bit contributions, scatters run
``scatter_rows`` once per device.  The property under test is exact row
movement — gather → transform → scatter over the sharded arena must
leave *bit-identical* state to the same sequence over a replicated
arena, for arbitrary cohorts (sentinel-padded, clients repeating across
rounds), any D, and sign-bit-hostile values like -0.0.

The engine-level contract (``arena="sharded"`` == ``arena="replicated"``
through real ``shard_map`` collectives, full runs) lives in
``tests/sharded_arena_check.py`` — a subprocess, because the
virtual-device override must precede jax init.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subprocess import run_check
from repro.data import partition
from repro.fed import arena


# ---------------------------------------------------------------------------
# emulated-mesh routing
# ---------------------------------------------------------------------------

def make_plan(num_clients, d):
    rows = -(-(num_clients + 1) // d)
    return arena.ArenaPlan(num_clients, rows, ("clients",), (d,))


def split(full, plan):
    """Replicated padded arena -> per-device (L, …) blocks."""
    d, rows = plan.num_shards, plan.rows_per_shard
    return [jax.tree.map(lambda a: a[i * rows:(i + 1) * rows], full)
            for i in range(d)]


def emu_gather(plan, shards, cids):
    """Sum of the per-device bit contributions — the psum, emulated."""
    contribs = [arena.take_rows(plan, s, cids, i)
                for i, s in enumerate(shards)]
    summed = jax.tree.map(lambda *xs: sum(xs[1:], start=xs[0]), *contribs)
    return jax.tree.map(lambda b, a: arena.from_bits(b, a.dtype),
                        summed, shards[0])


def ef_step(rows):
    """A stand-in compress: top-2-magnitude values leave, the remainder
    stays as residual — the error-feedback shape of the real topk path,
    applied to whatever the gather returned."""
    k = min(2, rows.shape[1])
    thresh = -jnp.sort(-jnp.abs(rows), axis=1)[:, k - 1:k]
    sent = jnp.where(jnp.abs(rows) >= thresh, rows, 0.0)
    return rows - sent


def run_rounds(num_clients, d, cohorts, values, width=3):
    """Drive gather → ef_step → scatter for every cohort over both a
    sharded and a replicated arena; return both final arenas plus the
    per-round gathered rows of each (for row-identity asserts)."""
    plan = make_plan(num_clients, d)
    full = jnp.zeros((plan.total_rows, width), jnp.float32)
    full = full.at[:num_clients].set(values)
    ref = full
    shards = split(full, plan)
    got_rows, ref_rows = [], []
    for cids in cohorts:
        cids = jnp.asarray(cids, jnp.int32)
        live = cids < num_clients
        g = emu_gather(plan, shards, cids)
        r = ref[cids]
        got_rows.append(np.asarray(g))
        ref_rows.append(np.asarray(r))
        shards = [arena.scatter_rows(plan, s, ef_step(g), cids, live, i)
                  for i, s in enumerate(shards)]
        safe = jnp.where(live, cids, plan.total_rows)   # drop sentinels
        ref = ref.at[safe].set(ef_step(r), mode="drop")
    rebuilt = jnp.concatenate(shards, axis=0)
    return np.asarray(rebuilt), np.asarray(ref), got_rows, ref_rows


def draw_cohorts(rng, num_clients, s, rounds):
    """Per-round without-replacement cohorts, sentinel-padded to S;
    clients repeat freely *across* rounds."""
    out = []
    for _ in range(rounds):
        take = min(s, num_clients)
        c = rng.choice(num_clients, size=take, replace=False)
        out.append(np.concatenate(
            [c, np.full(s - take, num_clients)]).astype(np.int32))
    return out


def check_roundtrip(num_clients, d, s, rounds, seed):
    rng = np.random.default_rng(seed)
    values = rng.normal(size=(num_clients, 3)).astype(np.float32)
    # plant sign-bit hazards: a float psum would flip these
    values[rng.random(values.shape) < 0.2] = -0.0
    cohorts = draw_cohorts(rng, num_clients, s, rounds)
    got, ref, got_rows, ref_rows = run_rounds(num_clients, d, cohorts,
                                              values)
    for t, (g, r) in enumerate(zip(got_rows, ref_rows)):
        np.testing.assert_array_equal(
            g.view(np.uint32), r.view(np.uint32),
            err_msg=f"I={num_clients} D={d} round {t}: gathered rows")
    np.testing.assert_array_equal(
        got.view(np.uint32), ref.view(np.uint32),
        err_msg=f"I={num_clients} D={d}: final arena")


def test_gather_scatter_roundtrip_grid():
    """Deterministic grid (always runs): D ∈ {1, 2, 4} × populations
    that pad / divide / exceed the shard count, cohorts with sentinel
    slots, clients revisited across 5 rounds."""
    for d in (1, 2, 4):
        for num_clients, s in ((3, 2), (7, 3), (8, 4), (10, 4), (4, 5)):
            check_roundtrip(num_clients, d, s, rounds=5, seed=31 * d + s)


def test_gather_scatter_roundtrip_property():
    hyp = pytest.importorskip("hypothesis")
    given, settings, st = hyp.given, hyp.settings, hyp.strategies

    @given(num_clients=st.integers(1, 24), d=st.sampled_from([1, 2, 4]),
           s=st.integers(1, 8), rounds=st.integers(1, 6),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=40, deadline=None)
    def check(num_clients, d, s, rounds, seed):
        check_roundtrip(num_clients, d, s, rounds, seed)

    check()


def test_address_matches_host_addressing():
    """arena.address (trace-time) == partition.home_addressing (host) on
    the same plan, sentinel included."""
    for num_clients, d in ((5, 2), (10, 4), (7, 3)):
        plan = make_plan(num_clients, d)
        cohorts = np.array([[0, num_clients, 3],
                            [num_clients - 1, 1, num_clients]])
        home_h, row_h = partition.home_addressing(
            cohorts, plan.rows_per_shard)
        home_t, row_t = arena.address(plan, jnp.asarray(cohorts))
        np.testing.assert_array_equal(np.asarray(home_t), home_h)
        np.testing.assert_array_equal(np.asarray(row_t), row_h)
        assert home_h.max() < plan.num_shards   # sentinel homes on-mesh


def test_sentinel_reads_zero_and_writes_drop():
    plan = make_plan(4, 2)                      # L = ceil(5/2) = 3
    full = jnp.arange(plan.total_rows * 2, dtype=jnp.float32)
    full = full.reshape(plan.total_rows, 2).at[4:].set(0.0)
    shards = split(full, plan)
    cids = jnp.asarray([4, 1], jnp.int32)       # sentinel + live
    g = emu_gather(plan, shards, cids)
    np.testing.assert_array_equal(np.asarray(g[0]), 0.0)
    live = cids < 4
    out = [arena.scatter_rows(plan, s, jnp.full((2, 2), 7.0), cids, live, i)
           for i, s in enumerate(shards)]
    rebuilt = np.concatenate([np.asarray(o) for o in out])
    np.testing.assert_array_equal(rebuilt[4:], 0.0)   # dead rows stay dead
    np.testing.assert_array_equal(rebuilt[1], 7.0)


def test_arena_residency_at_100k_over_4_shards():
    """At I = 100,000 clients over D = 4 home shards, each device's
    block of the top-k error-feedback arena holds at most 1/D + 0.15 of
    what the replicated arena holds on every device.  The block is read
    from the sharding the engine builds (``make_plan`` and
    ``shard_spec`` on the mesh), on abstract 4-device meshes: the 1-D
    client mesh and the 2x2 group mesh."""
    from jax.sharding import AbstractMesh, NamedSharding
    from repro.fed import compression
    from repro.fed.tasks.mlp import MLPTask
    pop, d = 100_000, 4
    avals = jax.eval_shape(MLPTask().init_params, jax.random.key(0))
    ef = compression.topk(0.1)

    def resident_bytes(shapes, sharding=None):
        return sum(int(np.prod(x.shape if sharding is None
                               else sharding.shard_shape(x.shape)))
                   * x.dtype.itemsize for x in jax.tree.leaves(shapes))

    replicated = resident_bytes(
        jax.eval_shape(lambda: ef.init_client_state(avals, pop)))
    for mesh in (AbstractMesh((4,), ("clients",)),
                 AbstractMesh((2, 2), ("groups", "clients"))):
        plan = arena.make_plan(pop, mesh)
        sharded = resident_bytes(
            jax.eval_shape(lambda: ef.init_client_state(avals,
                                                        plan.total_rows)),
            NamedSharding(mesh, arena.shard_spec(plan)))
        assert sharded <= (1 / d + 0.15) * replicated, \
            (mesh.axis_names, sharded / replicated)


# ---------------------------------------------------------------------------
# engine-level A/B (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_arena_matches_replicated_2dev():
    run_check("sharded_arena_check.py", marker="SHARDED_ARENA_CHECK_OK")
