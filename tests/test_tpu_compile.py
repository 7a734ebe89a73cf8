"""The engine's kernels compiled for a described TPU v5e (nothing runs),
and ``chip_smoke.py``'s phase functions run small on the CPU.

The compiles go through Mosaic, which interpret-mode tests never reach:
``event_scan`` at the job-slot widths of the bench cells (pairwise rank
at J=32, bitonic rank at J=640 and J=2000), ``link_scan`` at the _net
cell's transfer table, ``event_frontier`` at the engine's segment
layouts, and ``event_scan`` under ``vmap`` as the lane-batched sweep
loop calls it.  The topology is described inside a fixture, so only the
worker that runs these tests loads the TPU compiler.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core import gridlet, resource, simulation, types
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("r,j,with_rank", [
    (16, 32, False), (16, 32, True),          # pairwise rank
    (16, 640, False), (16, 640, True),        # bitonic, padded to 1024
    (16, 2000, False), (16, 2000, True),      # bitonic, padded to 2048
    (8, 640, True),                           # the deep fleet's rows
])
def test_event_scan_compiles_for_v5e(one_chip, r, j, with_rank):
    table = jax.ShapeDtypeStruct((r, j), jnp.float32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((r,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda rem, tie, m, p, pol, blk, ok: ops.event_scan(
            rem, m, p, tie=tie, policy=pol, pe_blocked=blk, row_ok=ok,
            interpret=False, with_rank=with_rank),
        table, table, row, row, row, row, row)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("with_cap", [False, True])
def test_link_scan_compiles_for_v5e(one_chip, with_cap):
    table = jax.ShapeDtypeStruct((16, 640), jnp.float32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda rem, tie, baud, bg, cap: ops.link_scan(
            rem, baud, bg=bg, tie=tie, cap=cap if with_cap else None,
            interpret=False),
        table, table, row, row, row)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sizes", chip_smoke.FRONTIER_SIZES)
def test_event_frontier_compiles_for_v5e(one_chip, sizes):
    c = sum(sizes)
    vec = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda cand, cuts: ops.event_frontier(cand, sizes, cuts=cuts,
                                              interpret=False),
        vec, vec)
    assert "tpu_custom_call" in text


def test_vmapped_event_scan_compiles_for_v5e(one_chip):
    """Seven scenario lanes of [16, 640] tables, as the lane-batched
    sweep loop hands them to the kernel."""
    lanes, r, j = 7, 16, 640
    table = jax.ShapeDtypeStruct((lanes, r, j), jnp.float32,
                                 sharding=one_chip)
    row = jax.ShapeDtypeStruct((lanes, r), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        jax.vmap(lambda rem, tie, m, p: ops.event_scan(
            rem, m, p, tie=tie, interpret=False, with_rank=True)),
        table, table, row, row)
    assert "tpu_custom_call" in text


# ----------------------------------------------------------------------
# chip_smoke's phases, small, on the CPU (XLA route, no chip needed).
# ----------------------------------------------------------------------
def test_smoke_kernels_phase_small():
    rec = chip_smoke.phase_kernels(event_widths=((8, 12), (8, 600)),
                                   link_widths=((8, 130),),
                                   frontier_sizes=((3, 0, 5, 1),))
    assert rec["calls"] == 6


@pytest.mark.parametrize("spec", [
    (2, 6, None, None, 600.0, 6000.0, None),
    (2, 6, simulation.Scenario(baud_rate=28_000.0, bg_flows=1.0), None,
     600.0, 6000.0, dict(suffix="_net", net=True, in_bytes=200_000.0,
                         out_bytes=100_000.0)),
])
def test_smoke_engine_phase_small(spec):
    rec = chip_smoke.phase_engine(spec)
    assert rec["n_done"] == 12.0
    assert rec["pallas_calls"] == 0     # the CPU runs the XLA fallbacks


def test_smoke_paper_phase():
    rec = chip_smoke.phase_paper()
    assert rec["quickstart_done"] == 182


def test_smoke_sweeps_phase_small():
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(5), n_jobs=4, n_users=2)
    grid = (g, fleet, jnp.asarray([700.0, 1400.0]),
            jnp.asarray([6000.0, 14000.0]),
            simulation.Scenario(sched_min_period=10.0, sched_frac=0.05), 2)
    ps = [simulation._scenario_params(fleet, 700.0, 9000.0,
                                      types.OPT_COST, 2, sc)
          for sc in (simulation.Scenario(policy=types.OPT_COST),
                     simulation.Scenario(policy=types.OPT_TIME,
                                         pricing_model="auction",
                                         auction_period=60.0, seed=5))]
    strategy = (g, fleet, 2, simulation._max_events(g.n, 2, 700.0, 1.0),
                ["cost", "time_auction"],
                jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps))
    rec = chip_smoke.phase_sweeps(grid, strategy)
    assert rec["sweep_lanes"] == 4 and rec["strategy_lanes"] == 2


def test_smoke_sharded_phase_on_forced_devices():
    """The four-chip phase on four forced host devices, in a child
    process so this one keeps its single CPU device."""
    code = """
        import jax, jax.numpy as jnp
        import chip_smoke
        from repro.core import gridlet, resource
        assert len(jax.devices()) == 4
        g = gridlet.task_farm(jax.random.PRNGKey(5), n_jobs=4, n_users=2)
        rec = chip_smoke.phase_sharded(
            jax.devices(), (g, resource.wwg_fleet(),
                            jnp.asarray([2.0, 1400.0]),
                            jnp.linspace(6000.0, 14000.0, 4), 2))
        assert rec["devices"] == [0, 1, 2, 3], rec
        print("OK")
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


def test_smoke_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert capsys.readouterr().out == ""
