"""Trip-count-aware HLO collective accounting — validated against scans
with known structure (this is the §Roofline data path)."""

import textwrap

import pytest

from repro.launch.hlo_analysis import (collective_bytes, split_computations,
                                       _trip_count)


HLO = textwrap.dedent("""\
    HloModule test

    %body.1 (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
      %p = (s32[], f32[64,64]) parameter(0)
      %ar = f32[64,64]{1,0} all-reduce(%x), replica_groups={}, to_apply=%sum
      ROOT %t = (s32[], f32[64,64]) tuple(%i, %ar)
    }

    %cond.1 (p: (s32[], f32[64,64])) -> pred[] {
      %p = (s32[], f32[64,64]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %c = s32[] constant(10)
      ROOT %lt = pred[] compare(%i, %c), direction=LT
    }

    ENTRY %main (a: f32[64,64]) -> f32[64,64] {
      %a = f32[64,64] parameter(0)
      %ag = f32[128,64]{1,0} all-gather(%a), dimensions={0}
      %w = (s32[], f32[64,64]) while(%init), condition=%cond.1, body=%body.1
      ROOT %r = f32[64,64] get-tuple-element(%w), index=1
    }
""")


class TestParser:
    def test_split_computations(self):
        comps = split_computations(HLO)
        assert {"body.1", "cond.1", "main"} <= set(comps)

    def test_trip_count_from_condition(self):
        comps = split_computations(HLO)
        assert _trip_count(comps["cond.1"], comps["body.1"]) == 10

    def test_in_loop_collectives_multiplied(self):
        cb = collective_bytes(HLO)
        # all-reduce: 64·64·4 B × 2 (ring factor) × 10 trips
        assert cb["bytes"]["all-reduce"] == 64 * 64 * 4 * 2 * 10
        assert cb["counts"]["all-reduce"] == 10
        # all-gather outside the loop: result 128·64·4, once
        assert cb["bytes"]["all-gather"] == 128 * 64 * 4
        assert cb["counts"]["all-gather"] == 1

    def test_body_constants_do_not_inflate_trips(self):
        """Dimension-sized constants in the body must not be read as trip
        counts (the bug this parser replaced)."""
        hlo = HLO.replace("%ar = f32[64,64]{1,0} all-reduce(%x)",
                          "%big = s32[] constant(4096)\n"
                          "  %ar = f32[64,64]{1,0} all-reduce(%x)")
        cb = collective_bytes(hlo)
        assert cb["counts"]["all-reduce"] == 10


@pytest.mark.slow
class TestAgainstRealLowering:
    def test_scan_collectives_counted_per_trip(self):
        import subprocess, sys, os
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("d",))
TRIPS = 7
def fn(x):
    def body(c, _):
        # Loop-VARIANT contraction: c @ c.T needs c re-gathered every trip
        # (loop-invariant operands get hoisted — that is not a parser bug).
        y = c @ jnp.swapaxes(c, 0, 1)
        y = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P("d", None)))
        return y / jnp.float32(64.0), None
    out, _ = jax.lax.scan(body, x, None, length=TRIPS)
    return out
with jax.set_mesh(mesh):
    comp = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32,
                             sharding=NamedSharding(mesh, P("d", None)))
    ).compile()
cb = collective_bytes(comp.as_text())
n = sum(cb["counts"].values())
assert n >= TRIPS, cb["counts"]
print("OK", cb["counts"])
"""
        r = subprocess.run([sys.executable, "-c", script], cwd=repo,
                           env=dict(os.environ,
                                    PYTHONPATH=os.path.join(repo, "src")),
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stderr
        assert "OK" in r.stdout
