"""Unit tests for the roofline HLO analyzer: trip-count-aware flop
accounting (XLA's cost_analysis counts while bodies once) and
collective-byte math."""
import jax
import jax.numpy as jnp
import pytest

from repro.utils.hlo import analyze_hlo, roofline_terms


def lowered_hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_trip_count_multiplies_dot_flops():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    n, L = 128, 12
    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    w = jax.ShapeDtypeStruct((L, n, n), jnp.float32)
    costs = analyze_hlo(lowered_hlo(f, x, w), 1)
    expect = 2.0 * n * n * n * L
    assert costs.dot_flops == pytest.approx(expect, rel=0.01), \
        (costs.dot_flops, expect, costs.trip_counts)
    assert L in costs.trip_counts.values()


def test_unrolled_matmul_flops_exact():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    costs = analyze_hlo(lowered_hlo(f, a, b), 1)
    assert costs.dot_flops == pytest.approx(2 * 64 * 256 * 32, rel=1e-6)


def test_roofline_terms_bottleneck_selection():
    t = roofline_terms(dot_flops=197e12, bytes_accessed=1.0,
                       collective_bytes=1.0)
    assert t["bottleneck"] == "compute"
    assert t["roofline_fraction"] == pytest.approx(1.0)
    t = roofline_terms(dot_flops=1.0, bytes_accessed=819e9,
                       collective_bytes=1.0)
    assert t["bottleneck"] == "memory"
    t = roofline_terms(dot_flops=1.0, bytes_accessed=1.0,
                       collective_bytes=100e9)
    assert t["bottleneck"] == "collective"


def test_collective_bytes_counted_with_group_size():
    """8-way psum of N floats ~ 2*N*4*(7/8) bytes per device."""
    import subprocess, sys
    from pathlib import Path
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS
from repro.utils.hlo import analyze_hlo
mesh = jax.make_mesh((8,), ("d",))
def f(x):
    return jax.shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                         in_specs=PS("d"), out_specs=PS(),
                         check_vma=False)(x)
x = jax.ShapeDtypeStruct((1024, 128), jnp.float32,
                         sharding=NamedSharding(mesh, PS("d")))
hlo = jax.jit(f).lower(x).compile().as_text()
c = analyze_hlo(hlo, 8)
expect = 2 * (1024 // 8) * 128 * 4 * (7 / 8)
assert abs(c.collective_bytes - expect) / expect < 0.05, \\
    (c.collective_bytes, expect)
print("OK")
"""
    import os
    repo = Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             # see tests/test_distributed.py: keep libtpu images on CPU
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
             "PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]
