"""HLO analyzer validation against computations with KNOWN costs.

The roofline numbers all flow through repro.launch.hlo_analysis, so its
FLOP/byte/trip-count accounting is validated here on small jit'd programs
whose true costs are computable by hand.
"""
import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_matmul_flops_exact():
    """(M,K)@(K,N) = 2*M*K*N flops."""
    M, K, N = 128, 256, 64
    a = jnp.zeros((M, K), jnp.float32)
    b = jnp.zeros((K, N), jnp.float32)
    c = analyze(_hlo(lambda x, y: x @ y, a, b))
    want = 2 * M * K * N
    assert want <= c.flops <= 1.1 * want + 1e4, (c.flops, want)


def test_scan_trip_count_multiplies():
    """A scan with T iterations must cost ~T x one body."""
    M = 128
    a = jnp.zeros((M, M), jnp.float32)

    def once(x):
        return x @ x

    def scanned(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=16)
        return y

    c1 = analyze(_hlo(once, a))
    c16 = analyze(_hlo(scanned, a))
    ratio = c16.flops / max(c1.flops, 1)
    assert 12 <= ratio <= 20, ratio  # 16 +- fusion noise


def test_elementwise_flops_scale_with_size():
    a = jnp.zeros((1 << 16,), jnp.float32)
    c = analyze(_hlo(lambda x: x * 2 + 1, a))
    assert c.flops >= (1 << 16)  # at least one flop per element
    assert c.flops <= 8 * (1 << 16)


def test_bytes_order_of_magnitude():
    """Elementwise op traffic ~ input + output bytes (within fusion factor)."""
    n = 1 << 20
    a = jnp.zeros((n,), jnp.float32)
    c = analyze(_hlo(lambda x: x + 1.0, a))
    want = 2 * 4 * n  # read + write
    assert 0.5 * want <= c.bytes <= 4 * want, (c.bytes, want)


def test_collective_detection():
    """psum under shard_map shows up as all-reduce bytes."""
    import subprocess, sys, textwrap, os, json
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as PS
        from repro.launch.hlo_analysis import analyze
        mesh = jax.make_mesh((4,), ("data",))
        f = jax.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                          in_specs=PS("data"), out_specs=PS(), check_vma=False)
        hlo = jax.jit(f).lower(jnp.zeros((1024,), jnp.float32)).compile().as_text()
        c = analyze(hlo)
        print(json.dumps({"ar": c.collectives.get("all-reduce", 0)}))
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=240,
                          cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    # 256 f32 elements per shard = 1 KiB of all-reduce payload
    assert res["ar"] >= 1024, res


def test_dynamic_slice_counted_as_slice_not_operand():
    """Slicing 1 row of a big array must NOT bill the whole array."""
    big = jnp.zeros((1024, 1024), jnp.float32)

    def f(x, i):
        return jax.lax.dynamic_slice_in_dim(x, i, 1, 0)

    c = analyze(_hlo(f, big, jnp.int32(0)))
    # full operand = 4 MB, slice = 4 KB.  The analyzer bills fused-slice
    # operands at max(32 x output, 1 MiB) — the 1 MiB floor protects
    # reduction fusions from being undercounted — so the acceptable bound
    # here is ~1 MiB, NOT the 4 MB naive full-operand accounting.
    assert c.bytes < 1.2e6, c.bytes


def test_bytes_by_op_histogram_sums():
    a = jnp.zeros((256, 256), jnp.float32)
    c = analyze(_hlo(lambda x: (x @ x) + x, a))
    assert abs(sum(c.bytes_by_op.values()) - c.bytes) < 1.0
    assert c.bytes_by_op.get("dot", 0) + c.bytes_by_op.get("fusion", 0) > 0
