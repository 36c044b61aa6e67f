"""Distribution layer: sharding rules, HLO analyzer, and multi-device
behaviour (subprocesses own the forced device count so the main test
process keeps seeing 1 real device)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_spec_for_rules():
    from jax.sharding import PartitionSpec as P
    import jax
    from repro.sharding.rules import DEFAULT_PARAM_RULES, spec_for
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # axes that don't divide fall back to replication
    s = spec_for(("vocab", "embed"), DEFAULT_PARAM_RULES, mesh, (100, 64))
    assert s == P("model", "data") or s == P("model", "data")


def test_hlo_analyzer_counts_scan_trips():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.hlo_stats import analyze_hlo
        def f(x, w):
            def body(c, wi): return c @ wi, None
            y, _ = jax.lax.scan(body, x, w)
            return y.sum()
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((128, 128), jnp.float32),
            jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)).compile()
        r = analyze_hlo(c.as_text(), 1, 1)
        print(r['flops'])
    """, devices=1)
    flops = float(out.strip().splitlines()[-1])
    assert flops == pytest.approx(6 * 2 * 128**3, rel=0.01)


def test_sharded_hamming_topk():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.search import hamming_topk_sharded, hamming_topk
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        codes = rng.integers(0, 2**32, (1024, 2), dtype=np.uint32)
        q = rng.integers(0, 2**32, (2,), dtype=np.uint32)
        d1, i1 = hamming_topk_sharded(jnp.asarray(codes), jnp.asarray(q),
                                      8, mesh)
        d2, i2 = hamming_topk(jnp.asarray(codes), jnp.asarray(q), 8)
        assert list(np.asarray(d1)) == list(np.asarray(d2)), (d1, d2)
        print("ok")
    """)
    assert "ok" in out


@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_sharded_grouped_hamming_topk(shards, use_kernel):
    """sharded_topk_lanes == the single-device grouped scan, bit for bit:
    even and ragged shard sizes (a ragged table's last shards carry dead
    padding rows, flagged by ``active``), ties across shard boundaries,
    and l > n sentinels surviving the shard offset."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.search import (DIST_SENTINEL, hamming_topk_grouped,
                                       lane_rows, sharded_topk_lanes,
                                       to_lanes)
        uk = {use_kernel}
        shards = {shards}
        mesh = jax.make_mesh((shards,), ("data",))

        def scan(codes, qs, l):
            g, n, w = codes.shape
            n_local = -(-n // shards)
            codes = np.pad(codes, ((0, 0), (0, shards * n_local - n), (0, 0)))
            lanes = jnp.concatenate(
                [to_lanes(jnp.asarray(codes[:, s * n_local:][:, :n_local]),
                          lane_rows(n_local)) for s in range(shards)], axis=2)
            lanes = jax.device_put(
                lanes, NamedSharding(mesh, P(None, None, "data", None)))
            active = None
            if shards * n_local > n:
                active = jax.device_put(np.arange(shards * n_local) < n,
                                        NamedSharding(mesh, P("data")))
            return sharded_topk_lanes(lanes, jnp.asarray(qs), l, mesh,
                                      "data", n_local, active, use_kernel=uk)

        rng = np.random.default_rng(0)
        cases = [(3, 512, 4, 2, 16),    # even shards
                 (2, 1001, 3, 2, 8),    # ragged: 1001 rows over shards
                 (2, 37, 3, 2, 40),     # ragged AND l > n
                 (1, 5, 2, 1, 12)]      # tiny group, l > n
        for (g, n, b, w, l) in cases:
            codes = rng.integers(0, 2**32, (g, n, w), dtype=np.uint32)
            qs = rng.integers(0, 2**32, (g, b, w), dtype=np.uint32)
            dw, iw = hamming_topk_grouped(jnp.asarray(codes),
                                          jnp.asarray(qs), l)
            dg, ig = scan(codes, qs, l)
            assert np.array_equal(np.asarray(dg), np.asarray(dw)), (g, n, l)
            assert np.array_equal(np.asarray(ig), np.asarray(iw)), (g, n, l)
            if l > n:   # sentinel tail intact after the offset/merge
                assert (np.asarray(dg)[..., n:] == DIST_SENTINEL).all()
                assert (np.asarray(ig)[..., n:] == -1).all()
        # massive ties spanning every shard boundary: lowest global id wins
        codes = np.zeros((2, 103, 2), np.uint32)
        qs = rng.integers(0, 2**32, (2, 3, 2), dtype=np.uint32)
        dw, iw = hamming_topk_grouped(jnp.asarray(codes), jnp.asarray(qs), 60)
        dg, ig = scan(codes, qs, 60)
        assert np.array_equal(np.asarray(ig), np.asarray(iw))
        assert np.array_equal(np.asarray(dg), np.asarray(dw))
        print("ok")
    """, devices=shards)
    assert "ok" in out


def test_sharded_query_scan_batch():
    """MultiTableIndex fitted on row-sharded features (its mesh read from
    x.sharding) == the one-device index on the same rows: query_scan_batch,
    scan_table_topk and the scan-mode service, three tables, before and
    after delete churn and auto-compaction — which leaves the shards
    ragged (each keeps its own live rows, the fuller shards set the block
    size and the others pad it with dead rows); stable ids survive."""
    out = _run("""
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.indexer import IndexConfig
        from repro.data.synthetic import tiny1m_like
        from repro.serving import HashQueryService, MultiTableIndex
        corpus = tiny1m_like(n_labeled=700, n_unlabeled=0, d=32, classes=5,
                             seed=0)
        x = corpus.x[:600]                           # 150 rows a shard
        rng = np.random.default_rng(1)
        ws = rng.normal(size=(8, x.shape[1])).astype(np.float32)
        mesh = jax.make_mesh((4,), ("data",))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        cfg = IndexConfig(method="bh", bits=18, tables=3)
        one = MultiTableIndex(cfg).fit(x)
        mt = MultiTableIndex(cfg).fit(xs)
        assert (mt.mesh, mt.axis) == (mesh, "data")
        assert mt.stats()["shards"] == 4
        assert mt.stats()["rows_per_shard"] == 150

        def same(mask=None):
            a = one.query_scan_batch(ws, l=16, topk=4, mask=mask)
            b = mt.query_scan_batch(ws, l=16, topk=4, mask=mask)
            assert np.array_equal(a.ids, b.ids)
            assert np.array_equal(a.margins, b.margins)
            assert np.array_equal(a.ids_topk, b.ids_topk)
            assert np.array_equal(a.margins_topk, b.margins_topk)
            assert np.array_equal(a.table_hits, b.table_hits)
            for i in range(8):
                assert np.array_equal(a.candidates[i], b.candidates[i])
            for got, want in zip(mt.scan_table_topk(ws, l=16),
                                 one.scan_table_topk(ws, l=16)):
                assert np.array_equal(got, want)
            return b

        same()
        # tombstones: a delete flags rows dead on their chip
        gone = rng.choice(600, 200, replace=False)
        one.delete(gone)
        mt.delete(gone)
        assert mt.compactions == 0 and mt.n == 400
        b = same()
        assert not np.isin(b.ids, gone).any()
        same(rng.random(600) < 0.5)
        # past half dead, auto-compaction: the shards keep different live
        # counts, so all but the fullest carry dead padding rows
        more = rng.choice(np.setdiff1d(np.arange(600), gone), 110,
                          replace=False)
        one.delete(more)
        mt.delete(more)
        assert mt.compactions == 1 == one.compactions, mt.compactions
        live = np.setdiff1d(np.arange(600), np.concatenate([gone, more]))
        per_shard = np.bincount(live // 150, minlength=4)
        assert len(set(per_shard.tolist())) > 1, per_shard
        assert mt.stats()["rows_per_shard"] == per_shard.max()
        assert mt.n == 290 and mt.stats()["rows"] == 290
        b = same()
        assert np.isin(b.ids[b.nonempty], live).all()    # stable ids
        same(rng.random(600) < 0.5)
        assert np.array_equal(mt.compact(), live)        # nothing dead: no-op
        try:
            mt.delete(gone[:1])
            raise AssertionError("deleted a compacted-away id")
        except KeyError:
            pass
        one.delete(live[:5])
        mt.delete(live[:5])
        same()
        svc = HashQueryService(mt, max_batch=8, mode="scan", scan_l=16)
        got = svc.query_batch(ws)
        assert [r.index for r in got] == (
            one.query_scan_batch(ws, l=16).ids.tolist())
        assert svc.stats()["requests"] == 8
        for op, arg in (("insert", x[:2]), ("lookup_batch", ws)):
            try:
                getattr(mt, op)(arg)
            except NotImplementedError:
                continue
            raise AssertionError(op)
        print("ok")
    """, devices=4)
    assert "ok" in out


def test_compressed_psum_error_feedback():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_compress import compressed_psum, init_residuals
        mesh = jax.make_mesh((4,), ("dp",))
        g = {"w": jnp.asarray(np.random.default_rng(0)
                              .normal(size=(4, 256)).astype(np.float32))}
        r0 = {"w": jnp.zeros((256,), jnp.float32)}
        def f(gs, rs):
            return compressed_psum(gs, rs, "dp")
        out = jax.jit(jax.shard_map(f, mesh=mesh,
                                    in_specs=(P("dp"), P()),
                                    out_specs=P(), check_vma=False))(
            {"w": g["w"]}, r0)
        mean_g, new_r = out
        exact = np.asarray(g["w"]).reshape(4, 256).mean(0)
        err = np.abs(np.asarray(mean_g["w"]) - exact).max()
        scale = np.abs(exact).max()
        assert err < 0.05 * scale + 1e-3, err
        print("ok")
    """, devices=4)
    assert "ok" in out


def test_dryrun_cell_reduced_mesh():
    """The dry-run driver end-to-end on an 8-device debug mesh."""
    out = _run("""
        import os
        os.environ["REPRO_DRYRUN_DEVICES"] = "8"
        import sys
        sys.argv = ["dryrun"]
        import importlib
        m = importlib.import_module("repro.launch.dryrun")
        # monkeypatch the production mesh to the debug size
        import jax
        from jax.sharding import AxisType
        import repro.launch.dryrun as dr
        auto = lambda k: (AxisType.Auto,) * k   # as make_production_mesh
        dr.make_production_mesh = lambda multi_pod=False: (
            jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=auto(3)) if multi_pod
            else jax.make_mesh((4, 2), ("data", "model"), axis_types=auto(2)))
        rec = dr.run_cell("qwen3-1.7b", "train_4k", False, None)
        assert rec["flops_per_device"] > 0
        assert rec["memory"]["peak_bytes"] > 0
        rec2 = dr.run_cell("qwen3-1.7b", "decode_32k", True, None)
        assert rec2["kind"] == "decode"
        print("ok")
    """, devices=8)
    assert "ok" in out
