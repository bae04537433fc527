"""HLO walker: trip-count-aware flop/collective accounting vs analytic."""

import subprocess
import sys
import os

import pytest

from repro.launch import hlo_analysis as H


def test_parse_tuple_types_with_comments():
    txt = """
ENTRY %main (p: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  %t = (s32[], f32[4,4]{1,0}, /*index=2*/f32[8]{0}) tuple(%p)
  ROOT %d = f32[4,4]{1,0} dot(%p, %p), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    cost = H.analyze_hlo_text(txt)
    assert cost.flops == 2 * 16 * 4


def test_while_trip_count_multiplies():
    txt = """
%cond (c: (s32[], f32[4,4])) -> pred[] {
  %c = (s32[], f32[4,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %k = s32[] constant(11)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (b: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %b = (s32[], f32[4,4]{1,0}) parameter(0)
  %x = f32[4,4]{1,0} get-tuple-element(%b), index=1
  %d = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i2 = s32[] get-tuple-element(%b), index=0
  ROOT %t = (s32[], f32[4,4]{1,0}) tuple(%i2, %d)
}

ENTRY %main (p: f32[4,4]) -> (s32[], f32[4,4]) {
  %p = f32[4,4]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[4,4]{1,0}) tuple(%zero, %p)
  ROOT %w = (s32[], f32[4,4]{1,0}) while(%init), condition=%cond, body=%body
}
"""
    cost = H.analyze_hlo_text(txt)
    assert cost.flops == 11 * 2 * 16 * 4


def test_collective_bytes():
    txt = """
ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  ROOT %ar = f32[16,16]{1,0} all-reduce(%p), replica_groups={}
}
"""
    cost = H.analyze_hlo_text(txt)
    assert cost.coll_bytes == 16 * 16 * 4
    assert cost.coll_counts == {"all-reduce": 1}


CHECK = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import sys
sys.path.insert(0, %r)
from repro.launch import hlo_analysis as H

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
L, D = 7, 256

def f(ws, x):
    def body(h, w):
        h = jnp.dot(h, w, preferred_element_type=jnp.float32)
        h = jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, P("data", None)))
        return h.astype(x.dtype), None
    return jax.lax.scan(body, x, ws)[0]

comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, None, "model")),
                                NamedSharding(mesh, P("data", None))),
               out_shardings=NamedSharding(mesh, P("data", None))).lower(
    jax.ShapeDtypeStruct((L, D, D), jnp.float32),
    jax.ShapeDtypeStruct((64, D), jnp.float32)).compile()
c = H.analyze_hlo_text(comp.as_text())
assert c.flops == 2 * 32 * 256 * 256 * 7, c.flops
assert c.coll_bytes == 256 * 64 * 4 * 7, c.coll_bytes
assert c.coll_counts.get("all-gather") == 7, c.coll_counts
print("HLO-OK")
"""


def test_against_real_compile():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src
    out = subprocess.run([sys.executable, "-c", CHECK % src],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "HLO-OK" in out.stdout
