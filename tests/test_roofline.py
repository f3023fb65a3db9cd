"""The trip-count-aware HLO analyzer: known-flops programs, loop
multiplication, collective accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo import HloModule, analyze_hlo_text, shape_bytes


def _compiled_text(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_shape_bytes():
    assert shape_bytes("bf16[4,8]{1,0}") == 64
    assert shape_bytes("f32[10]") == 40
    assert shape_bytes("(f32[2,2], s32[3])") == 28
    assert shape_bytes("pred[]") == 1


def test_scan_flops_multiplied_by_trip_count():
    n, trip = 128, 10

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=trip)
        return y

    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    w = jax.ShapeDtypeStruct((n, n), jnp.float32)
    tot = analyze_hlo_text(_compiled_text(f, x, w))
    dot_flops = 2 * n * n * n * trip
    assert tot.flops >= dot_flops, "trip count must multiply body flops"
    assert tot.flops < dot_flops * 1.5, "flops should not explode"


def test_nested_scan_multiplies():
    n, inner, outer = 64, 4, 6

    def f(x, w):
        def obody(c, _):
            def ibody(cc, _):
                return cc @ w, None
            cc, _ = jax.lax.scan(ibody, c, None, length=inner)
            return cc, None
        y, _ = jax.lax.scan(obody, x, None, length=outer)
        return y

    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    w = jax.ShapeDtypeStruct((n, n), jnp.float32)
    tot = analyze_hlo_text(_compiled_text(f, x, w))
    expected = 2 * n ** 3 * inner * outer
    assert expected <= tot.flops <= expected * 1.3


def test_unrolled_matches_scan():
    n = 64

    def scan_f(x, w):
        def body(c, _):
            return c @ w, None
        return jax.lax.scan(body, x, None, length=8)[0]

    def unroll_f(x, w):
        for _ in range(8):
            x = x @ w
        return x

    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    w = jax.ShapeDtypeStruct((n, n), jnp.float32)
    ts = analyze_hlo_text(_compiled_text(scan_f, x, w))
    tu = analyze_hlo_text(_compiled_text(unroll_f, x, w))
    np.testing.assert_allclose(ts.flops, tu.flops, rtol=0.05)


def test_collectives_counted_with_loop_multiplier():
    import os
    import subprocess
    import sys
    # needs >1 device: run in a subprocess with forced host devices
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
import sys
sys.path.insert(0, "src")
from repro.roofline.hlo import analyze_hlo_text
from repro.sharding.compat import make_mesh

mesh = make_mesh((4,), ("d",))
def f(x):
    def body(c, _):
        s = jax.shard_map(lambda a: jax.lax.psum(a, "d"), mesh=mesh,
                          in_specs=P("d"), out_specs=P("d"))(c)
        return c + s * 0.1, None
    y, _ = jax.lax.scan(body, x, None, length=5)
    return y
x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
sh = NamedSharding(mesh, P("d"))
txt = jax.jit(f, in_shardings=sh).lower(x).compile().as_text()
tot = analyze_hlo_text(txt)
ar = tot.coll_bytes["all-reduce"]
# per-partition operand (2,128) f32 = 1024 B, x5 iterations
assert ar >= 1024 * 5, f"all-reduce bytes {ar}"
print("OK", ar)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_dus_counted_in_place():
    """decode-style cache update must cost the slice, not the buffer
    (with donation, as serving loops use)."""
    def f(cache, upd):
        return jax.lax.dynamic_update_slice_in_dim(cache, upd, 5, axis=0)

    cache = jax.ShapeDtypeStruct((4096, 128), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 128), jnp.float32)
    txt = jax.jit(f, donate_argnums=0).lower(cache, upd).compile().as_text()
    tot = analyze_hlo_text(txt)
    full_io = 4096 * 128 * 4 * 2
    assert tot.hbm_bytes < full_io / 10, tot.hbm_bytes
