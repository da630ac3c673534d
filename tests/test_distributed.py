"""Multi-device behaviour (subprocess: host-platform device count is fixed
at first jax init, so sharded tests get their own interpreter)."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import subprocess_env


def _run(code: str, devices: int = 8, timeout: int = 420) -> str:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=subprocess_env(devices))
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_distributed_bfs_matches_oracle():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import EngineCaps
        from repro.core.distributed_bfs import make_distributed_pbfs
        from repro.data.treegen import TreeSpec, make_edge_table, bfs_reference
        from repro.launch.mesh import make_mesh

        spec = TreeSpec(num_vertices=2049, height=9, payload_cols=2, seed=3)
        table = make_edge_table(spec)
        src = np.asarray(table.column("from")); dst = np.asarray(table.column("to"))
        mesh = make_mesh((8,), ("data",))
        caps = EngineCaps(frontier=1024, result=1024)
        fn = make_distributed_pbfs(mesh, ("data",), spec.num_vertices,
                                   caps=caps, max_depth=6, num_payload_cols=2)
        pay = np.asarray(table.column("column1"))
        sh = NamedSharding(mesh, P("data"))
        gpos, vals, counts, depths, ovfs = fn(
            jax.device_put(src, sh), jax.device_put(dst, sh),
            jax.device_put(pay, sh), jnp.int32(0))
        gpos = np.asarray(gpos)
        got = set(int(x) for x in gpos if x >= 0)
        ref = set().union(*bfs_reference(src, dst, 0, 6, spec.num_vertices)[:7])
        assert got == ref, (len(got), len(ref))
        # late materialization: values match the gathered positions
        vals = np.asarray(vals); e_loc = src.shape[0] // 8
        for s in range(8):
            for j in range(1024):
                g = gpos[s*1024 + j]
                if g >= 0:
                    lp = g - s*e_loc
                    assert np.allclose(vals[s*1024 + j], pay[s*e_loc + lp])
        print("OK")
    """)
    assert "OK" in out


def test_shard_map_dp_with_grad_compression():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import psum_compressed
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4,), ("data",))
        g_local = {"w": jnp.arange(4.0)[:, None] + jnp.arange(3.0)[None, :]}

        def body(gs, scheme, res):
            red, res2 = psum_compressed(gs, "data", scheme, res)
            return red["w"]

        x = jax.device_put(jnp.stack([g_local["w"]]*4),
                           jax.sharding.NamedSharding(mesh, P("data")))
        for scheme in ("none", "bf16", "int8_ef"):
            res = {"w": jnp.zeros((4, 3))} if scheme == "int8_ef" else None
            fn = jax.shard_map(
                lambda xs: body({"w": xs[0]}, scheme, res),
                mesh=mesh, in_specs=P("data"), out_specs=P(),
                check_vma=False)
            out = fn(x)
            err = float(jnp.max(jnp.abs(out - g_local["w"])))
            tol = {"none": 1e-6, "bf16": 0.05, "int8_ef": 0.1}[scheme]
            assert err <= tol, (scheme, err)
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_checkpoint_elastic_remesh():
    """Save params sharded on 8 devices; restore onto a 4-device mesh."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.launch.mesh import make_mesh

        d = tempfile.mkdtemp()
        mesh8 = make_mesh((8,), ("data",))
        w = jnp.arange(64.0).reshape(8, 8)
        ws = jax.device_put(w, NamedSharding(mesh8, P("data")))
        path = save_checkpoint(d, 3, {"w": ws})

        mesh4 = make_mesh((4,), ("data",))
        sh4 = {"w": NamedSharding(mesh4, P("data"))}
        r = restore_checkpoint(path, {"w": w}, shardings=sh4)
        assert r["w"].sharding.mesh.shape["data"] == 4
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(w))
        print("OK")
    """)
    assert "OK" in out


def test_int8_error_feedback_reduces_bias():
    out = _run("""
        import jax.numpy as jnp, numpy as np
        from repro.distributed.collectives import compress_int8_ef, decompress_int8
        g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(512) * 1e-3)}
        res = {"w": jnp.zeros(512)}
        # accumulate the same gradient repeatedly; EF keeps the mean unbiased
        acc, n = jnp.zeros(512), 24
        for _ in range(n):
            q, s, res = compress_int8_ef(g, res)
            acc = acc + decompress_int8(q, s)["w"]
        err = float(jnp.max(jnp.abs(acc / n - g["w"])))
        raw_q, raw_s, _ = compress_int8_ef(g, {"w": jnp.zeros(512)})
        raw_err = float(jnp.max(jnp.abs(decompress_int8(raw_q, raw_s)["w"] - g["w"])))
        assert err < raw_err * 0.5, (err, raw_err)
        print("OK")
    """, devices=1)
    assert "OK" in out
