"""Distribution: EP-vs-dense MoE equivalence, gradient compression,
pipeline, tensor-parallel sharded decode, pipeline-escape decode windows,
mini dry-run — all in a subprocess with 4 fake devices so the rest of the
suite keeps its single real device."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str):
    """Run ``code`` in a child with 4 forced CPU devices. A child that
    fails fails the test; one that hangs is killed by its timeout."""
    env = dict(os.environ)
    # cap per-device thread pools: fake devices on few cores can exhaust
    # threads under load (observed as SIGABRT in Eigen worker spawn)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 --xla_cpu_multi_thread_eigen=false"
    )
    env["PYTHONPATH"] = SRC
    env["OMP_NUM_THREADS"] = "1"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_moe_ep_matches_dense_on_mesh():
    run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import get_tiny
        from repro.models import build_model
        from repro.models.layers import MeshAxes
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        axes = MeshAxes(data=("data",), model="model", fsdp=True)
        cfg = get_tiny("qwen3-moe-30b-a3b").replace(capacity_factor=8.0)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        f = lambda impl: float(jax.jit(lambda p, b: m.loss(p, b, axes=axes, mesh=mesh, moe_impl=impl)[0])(params, batch))
        le, ld = f("ep"), f("dense")
        assert abs(le - ld) < 1e-3, (le, ld)
        print("ep==dense OK")
    """)


def test_moe_ep_small_batch_decode():
    """Per-shard tokens < model ranks (the decode regime) must still work."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_tiny
        from repro.models import build_model
        from repro.models.layers import MeshAxes
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        axes = MeshAxes(data=("data",), model="model", fsdp=False)
        cfg = get_tiny("qwen3-moe-30b-a3b").replace(capacity_factor=8.0)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, cfg.vocab_size)  # 6 tokens < 4-dev granularity
        def f(impl):
            _, outs = m.prefill(params, toks, active_sites=jnp.asarray([0], jnp.int32),
                                with_cache=False, moe_impl=impl, axes=axes, mesh=mesh)
            return np.asarray(outs["final"]["maxprob"])
        np.testing.assert_allclose(f("ep"), f("dense"), rtol=2e-3, atol=2e-3)
        print("small-batch ep OK")
    """)


def test_gradient_compression_and_pipeline():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import make_compressed_grad_allreduce, pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("pod", "data"))
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (33, 17)), "b": jnp.ones((5,))}
        r = jax.tree.map(jnp.zeros_like, g)
        out, res = make_compressed_grad_allreduce(mesh, "pod")(g, r)
        for k in g:
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(g[k]*2), atol=0.06, rtol=0.02)
        # error feedback: residual holds the quantization error
        assert float(sum(jnp.sum(jnp.abs(x)) for x in jax.tree.leaves(res))) > 0
        mesh2 = make_mesh((4,), ("stage",))
        W = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(2), (6, 3, 16))
        y = pipeline_apply(mesh2, "stage", lambda p, h: jnp.tanh(h @ p), W, x)
        ref = x
        for i in range(4): ref = jnp.tanh(ref @ W[i])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)
        print("compression+pipeline OK")
    """)


def test_mini_dryrun_multidev():
    """Lower+compile a tiny arch on a (2,2) mesh — the dry-run machinery
    end-to-end without the 512-device cost."""
    run_sub("""
        import jax, numpy as np
        import repro.launch.dryrun as DR
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 2)
        fn, args, donate = DR.build_cell("qwen2-1.5b", "train_4k", mesh,
            overrides=dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                           d_ff=128, vocab_size=2048, dtype="float32"))
        # shrink the batch via rebuilt abstracts is overkill; just compile
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        from repro.compat import cost_analysis
        ca = cost_analysis(compiled)
        assert ca.get("flops", 0) > 0
        cb = DR.collective_bytes(compiled.as_text())
        print("mini dryrun OK", sum(cb["bytes"].values()))
    """)


def test_sharded_decode_bit_identical():
    """`decode_sharded` / `decode_sharded_multi` at tp=2, tp=4 and
    dp=2 x tp=2 must be bit-identical to single-device `decode` — the
    tiled all_gather combine is a pure concatenation, so the sharded
    matmuls reduce in exactly the single-device order."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_tiny
        from repro.models.transformer import LM

        def eq_tree(a, b):
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            return len(la) == len(lb) and all(
                bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))

        cfg = get_tiny("qwen2-1.5b").replace(n_kv_heads=4)  # tp=4 needs 4 KV heads
        m = LM(cfg)
        params = m.init(jax.random.PRNGKey(0))
        B, S = 4, 8
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        cache, outs = m.prefill(params, toks, cache_len=32, moe_impl="dense")
        last = outs["final"]["label"].reshape(B, 1).astype(jnp.int32)
        pos = jnp.full((B,), S, jnp.int32)
        act = jnp.asarray([0, 1], jnp.int32)
        thr = jnp.asarray([0.5, 0.5], jnp.float32)
        c1, o1 = m.decode(params, cache, last, pos, active_sites=act,
                          moe_impl="dense", exit_thresholds=thr)
        shapes = [(1, 2), (1, 4), (2, 2)]
        for dp, tp in shapes:
            devs = np.array(jax.devices()[: dp * tp]).reshape(dp, tp)
            mesh = Mesh(devs, ("data", "model"))
            c2, o2 = m.decode_sharded(params, cache, last, pos, mesh=mesh,
                                      active_sites=act, moe_impl="dense",
                                      exit_thresholds=thr)
            assert eq_tree(o1, o2) and eq_tree(c1, c2), (dp, tp)
        # fused multi-step window, sharded vs single-device
        c4, rec1 = m.decode_multi(params, cache, last, pos, jnp.asarray(3),
                                  n_max=4, active_sites=act, thresholds=thr,
                                  moe_impl="dense")
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
        c5, rec2 = m.decode_sharded_multi(params, cache, last, pos,
                                          jnp.asarray(3), mesh=mesh, n_max=4,
                                          active_sites=act, thresholds=thr,
                                          moe_impl="dense")
        nd = int(rec1[4])
        assert int(rec2[4]) == nd
        for i, (a, b) in enumerate(zip(rec1[:4], rec2[:4])):
            assert bool(jnp.array_equal(a[:nd], b[:nd])), f"rec[{i}]"
        assert eq_tree(c4, c5)
        print("sharded decode OK")
    """)


def test_sharded_paged_decode_logits_match_single_device():
    """A paged decode step at tp=4 (params and pool placed in their
    shards) gives the final head's f32 logits of the same step on one
    device, within f32 rounding, and the same labels."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_tiny
        from repro.launch.mesh import make_serving_mesh
        from repro.models import build_model
        from repro.models import layers as LY

        cfg = get_tiny("qwen2-1.5b").replace(n_kv_heads=4, n_layers=2,
                                             decode_attn="paged")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        B, S, bs, nb = 4, 8, 4, 3
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        cache, outs = m.prefill(params, toks, cache_len=nb * bs, moe_impl="dense")

        def paged(x):  # row b's block j -> pool block 1 + b*nb + j
            blocks = x.reshape((x.shape[0], B * nb, bs) + x.shape[3:])
            return jnp.concatenate([jnp.zeros_like(blocks[:, :1]), blocks], 1)

        pool = {"blocks": jax.tree.map(paged, cache["blocks"])}
        last = outs["final"]["label"].reshape(B, 1).astype(jnp.int32)
        pos = jnp.full((B,), S, jnp.int32)
        tables = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
        _, o1 = m.decode(params, pool, last, pos, block_tables=tables,
                         moe_impl="dense", with_logits=True)
        mesh = make_serving_mesh(tp=4)

        def place(tree, specs):
            return jax.device_put(tree, jax.tree.map(
                lambda sp: NamedSharding(mesh, sp), specs,
                is_leaf=lambda x: isinstance(x, P)))

        p4 = place(params, m.tp_param_specs(LY.TEST_AXES))
        c4 = place(pool, m.tp_cache_specs(pool, LY.TEST_AXES))
        _, o4 = m.decode_sharded(p4, c4, last, pos, mesh=mesh,
                                 block_tables=tables, with_logits=True)
        l1 = np.asarray(o1["final"]["logits"])
        l4 = np.asarray(o4["final"]["logits"])
        assert l1.shape == (B, cfg.padded_vocab), l1.shape
        np.testing.assert_allclose(l4, l1, rtol=0, atol=1e-5)
        assert (np.asarray(o4["final"]["label"]) == l1.argmax(-1)).all()
        print("tp=4 logits OK")
    """)


def test_pipeline_decode_window_escapes():
    """Pipeline-parallel decode: thresholds-off windows are bit-identical
    to a plain per-step decode loop (tokens AND caches) at S=1/2/4; with a
    near-1.0 threshold at the stage-boundary ramps every row exits at
    stage 0 and later stages do strictly less work — the exit mask gates
    the ppermute forwarding."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_tiny
        from repro.models.transformer import LM
        from repro.distributed.pipeline import pipeline_decode_window, pipeline_check

        cfg = get_tiny("qwen2-1.5b").replace(n_layers=4)  # n_periods=4: 1/2/4 stages
        m = LM(cfg)
        params = m.init(jax.random.PRNGKey(0))
        B, S0, n_steps = 4, 8, 3
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S0), 0, cfg.vocab_size)
        cache, outs = m.prefill(params, toks, cache_len=32, moe_impl="dense")
        last = outs["final"]["label"].reshape(B, 1).astype(jnp.int32)
        pos = jnp.full((B,), S0, jnp.int32)
        ref_toks, c, t = [], cache, last
        for k in range(n_steps):
            c, o = m.decode(params, c, t, pos + k, moe_impl="dense")
            t = o["final"]["label"].reshape(B, 1).astype(jnp.int32)
            ref_toks.append(o["final"]["label"])
        ref_toks, ref_cache = jnp.stack(ref_toks), c

        def eq_tree(a, b):
            return all(bool(jnp.array_equal(x, y)) for x, y in
                       zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        for S in (1, 2, 4):
            mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
            nc, tok_rec, exit_rec, alive, steps = pipeline_decode_window(
                m, params, cache, last, pos, n_steps, mesh=mesh)
            assert bool(jnp.array_equal(tok_rec, ref_toks)), S
            assert eq_tree(nc, ref_cache), S
            assert bool(alive.all()) and int((exit_rec >= 0).sum()) == 0, S
        # exit-heavy: thr ~1.0 at every stage-boundary ramp
        sites = list(m.sites)
        for S in (2, 4):
            Lp, ns = m.plan.n_periods // S, len(m.plan.period)
            a = [sites.index(b) for b in
                 [(s + 1) * Lp * ns - 1 for s in range(S - 1)] if b in sites]
            assert a, f"S={S}: no boundary ramp in sites={sites}"
            mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
            nc, tok_rec, exit_rec, alive, steps = pipeline_decode_window(
                m, params, cache, last, pos, n_steps, mesh=mesh,
                active_sites=jnp.asarray(a, jnp.int32),
                thresholds=jnp.asarray([0.9999] * len(a), jnp.float32))
            assert int(steps[-1]) < int(steps[0]), (S, steps.tolist())
            assert int((exit_rec >= 0).sum()) > 0, S
        # rejection gates carry why-notes
        try:
            pipeline_check(LM(cfg.replace(decode_attn="paged")), 2)
            raise AssertionError("paged decode_attn should be rejected")
        except NotImplementedError as e:
            assert "block pool shards per-device" in str(e)
        try:
            pipeline_check(m, 3)
            raise AssertionError("n_periods % S != 0 should be rejected")
        except NotImplementedError:
            pass
        print("pipeline escapes OK")
    """)


def test_dryrun_merges_operator_xla_flags():
    """Importing `repro.launch.dryrun` must MERGE its 512-device default
    under any operator-exported XLA_FLAGS, never clobber them — the
    run_sub env already pins device_count=4, which must survive."""
    run_sub("""
        import os
        import repro.launch.dryrun  # noqa: F401  (import runs the env setup)
        flags = os.environ["XLA_FLAGS"]
        assert "--xla_force_host_platform_device_count=4" in flags, flags
        assert "512" not in flags, flags
        assert "--xla_cpu_multi_thread_eigen=false" in flags, flags
        # without an operator value the 512 default still lands
        from repro.launch.tuning import merge_xla_flags
        merged = merge_xla_flags("--xla_force_host_platform_device_count=512", None)
        assert merged == "--xla_force_host_platform_device_count=512", merged
        import jax
        assert jax.device_count() == 4, jax.device_count()
        print("dryrun flag merge OK")
    """)


def test_elastic_restore_across_meshes():
    """Checkpoint saved unsharded restores onto a different device layout."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save({"w": xs}, step=1)
        mesh2 = make_mesh((2, 2), ("a", "b"))
        tree = mgr.restore(1, sharding_tree={"w": NamedSharding(mesh2, P("b", "a"))})
        np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(x))
        print("elastic OK")
    """)
