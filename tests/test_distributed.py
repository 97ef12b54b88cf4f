"""Distribution substrate tests that need >1 device: run in a subprocess
with XLA_FLAGS forcing 8 host devices (smoke tests elsewhere must keep
seeing 1 device, so the flag never leaks into this process)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def run_in_subprocess(body: str, n_devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    assert "SUBPROC_OK" in r.stdout
    return r.stdout


def test_main_process_sees_one_device():
    import jax

    assert len(jax.devices()) == 1  # the dry-run flag must not leak


def test_sharded_train_step_runs_on_8_devices():
    run_in_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.distributed.mesh import make_mesh, plan_from_mesh
        from repro.distributed.sharding import (batch_shardings,
            param_shardings, shard_params)
        from repro.models.model import Model
        from repro.optim import adamw
        from repro.runtime.train_loop import (build_train_step,
            init_train_state)

        cfg = dataclasses.replace(reduced(get_config("granite-8b"),
            d_model=128), dtype="float32")
        mesh = make_mesh((2, 4), ("data", "model"))
        plan = plan_from_mesh(mesh)
        model = Model(cfg, plan=plan, attn_chunk=8, loss_chunk=8,
                      remat=False)
        opt = adamw(1e-3)
        step = build_train_step(model, opt)
        state = init_train_state(model, opt, jax.random.PRNGKey(0))
        state = state._replace(
            params=shard_params(cfg, plan, state.params))
        toks = jnp.zeros((4, 16), jnp.int32)
        batch = {"tokens": toks, "targets": toks}
        jit_step = jax.jit(step, donate_argnums=(0,))
        state, metrics = jit_step(state, batch)
        state, metrics = jit_step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    """)


def test_moe_shard_map_matches_single_device():
    """EP-sharded MoE must be numerically identical to the local path."""
    run_in_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.distributed.mesh import plan_from_mesh
        from repro.models.moe import init_moe, moe_block

        cfg = dataclasses.replace(
            reduced(get_config("qwen3-moe-235b-a22b"), d_model=64),
            dtype="float32", num_experts=8, experts_per_token=2,
            moe_capacity_factor=8.0)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = plan_from_mesh(mesh)
        p = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64))
        y_local, _ = moe_block(cfg, p, x)
        y_ep, aux_ep = jax.jit(lambda p_, x_: moe_block(
            cfg, p_, x_, mesh=mesh, dp_axes=("data",),
            tp_axis="model"))(p, x)
        np.testing.assert_allclose(np.asarray(y_ep),
                                   np.asarray(y_local),
                                   rtol=2e-4, atol=2e-4)
        # aux is grouped per data shard (GShard convention): compare
        # against the mean of per-shard local aux
        aux_shards = [float(moe_block(cfg, p, x[i:i + 2])[1])
                      for i in (0, 2)]
        np.testing.assert_allclose(float(aux_ep),
                                   sum(aux_shards) / 2, rtol=1e-4)
    """)


def test_elastic_remesh_reshards_params():
    run_in_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.models.model import Model, init_params
        from repro.runtime.elastic import ElasticController, plan_mesh

        cfg = dataclasses.replace(reduced(get_config("granite-8b"),
            d_model=128), dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        ctl = ElasticController(cfg, prefer_model=4)
        # full cluster: 8 devices
        p8, plan8 = ctl.remesh(params, jax.devices())
        # two nodes die -> 6 devices
        p6, plan6 = ctl.remesh(p8, jax.devices()[:6])
        assert plan6.mesh.devices.size == 6
        # values preserved across resharding
        a = jax.tree_util.tree_leaves(params)[0]
        b = jax.tree_util.tree_leaves(p6)[0]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        # loss computable on the shrunk mesh
        model = Model(cfg, plan=plan6, attn_chunk=8, loss_chunk=8,
                      remat=False)
        toks = jnp.zeros((6, 16), jnp.int32)
        loss, _ = jax.jit(model.loss)(p6, {"tokens": toks,
                                           "targets": toks})
        assert np.isfinite(float(loss))
    """)


def test_ring_allreduce_and_quantized_psum():
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import (psum_quantized,
            ring_allreduce)

        mesh = jax.make_mesh((8,), ("pod",))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)

        ring = jax.jit(jax.shard_map(
            lambda v: ring_allreduce(v, "pod", 8), mesh=mesh,
            in_specs=P("pod", None), out_specs=P("pod", None),
            check_vma=False))
        got = ring(x)
        want = jnp.tile(x.sum(0, keepdims=True), (8, 1))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

        qsum = jax.jit(jax.shard_map(
            lambda v: psum_quantized(v, "pod"), mesh=mesh,
            in_specs=P("pod", None), out_specs=P("pod", None),
            check_vma=False))
        got_q = qsum(x)
        # int8 quantization: bounded relative error vs exact psum
        err = np.abs(np.asarray(got_q) - np.asarray(want))
        assert err.max() <= np.abs(np.asarray(x)).max() / 127 * 8 + 1e-5
    """)


def test_tp_serving_matches_single_device():
    """tp=1/2/4 serving meshes are token-identical to the unset
    single-device engine through a fork -> explore -> commit cycle,
    including a lazy CoW fault serviced under shard_map, with the
    fused-dispatch count unchanged."""
    run_in_subprocess("""
        import dataclasses, jax
        from repro.configs import get_config
        from repro.models.model import Model
        from repro.runtime.serve_loop import ServeEngine

        cfg = dataclasses.replace(get_config("paper-agentic"),
                                  dtype="float32", num_layers=2)
        model = Model(cfg, attn_chunk=8, remat=False)
        params = model.init(jax.random.PRNGKey(0))

        def cycle(tp):
            eng = ServeEngine(model, params, num_pages=64, page_size=4,
                              max_pages_per_seq=16, tp=tp)
            sid = eng.add_request([1, 2, 3, 4, 5])
            toks = [eng.decode([sid])]
            kids = eng.fork(sid, 2)          # lazy CoW: faults on decode
            for _ in range(3):
                toks.append(eng.decode(kids))
            parent = eng.commit(kids[0])     # sibling invalidated
            toks.append(eng.decode([parent]))
            return toks, eng.cow_dispatches, eng.cow_faults, eng.tp

        base = cycle(None)
        assert base[3] == 1
        for tp in (1, 2, 4):
            got = cycle(tp)
            assert got[3] == tp
            assert got[:3] == base[:3], (tp, got, base)
    """, n_devices=4)


def test_tp_moe_serving_matches_single_device():
    """The expert-parallel decode arm (moe_apply_local under shard_map):
    a MoE engine at tp=2 is token-identical to single-device through a
    vectorized eager-CoW fan-out."""
    run_in_subprocess("""
        import dataclasses, jax
        from repro.configs import get_config, reduced
        from repro.models.model import Model
        from repro.runtime.serve_loop import ServeEngine

        cfg = dataclasses.replace(
            reduced(get_config("qwen3-moe-235b-a22b"), d_model=64),
            dtype="float32", num_experts=4, experts_per_token=2,
            num_kv_heads=2, moe_capacity_factor=8.0)
        model = Model(cfg, attn_chunk=8, remat=False)
        params = model.init(jax.random.PRNGKey(0))

        def cycle(tp):
            eng = ServeEngine(model, params, num_pages=64, page_size=4,
                              max_pages_per_seq=16, tp=tp)
            sid = eng.add_request([1, 2, 3, 4, 5])
            toks = [eng.decode([sid]) for _ in range(2)]
            kids = eng.fork(sid, 3, eager_cow=True)   # one fused CoW
            toks.append(eng.decode(kids))
            return toks, eng.cow_dispatches, eng.cow_faults

        assert cycle(None) == cycle(2)
    """, n_devices=2)


def test_tp_session_sampled_exploration_matches_single_device():
    """The full api stack (BranchSession -> Scheduler -> sharded engine)
    with temperature sampling: same prompts, same seed, tp=2 produces
    the same tokens as tp=1 through a vectorized branch() (eager fused
    CoW under shard_map), wait, score, first-commit-wins cycle."""
    run_in_subprocess("""
        import dataclasses, jax
        from repro.api import BranchSession
        from repro.configs import get_config
        from repro.models.model import Model
        from repro.runtime.serve_loop import ServeEngine

        cfg = dataclasses.replace(get_config("paper-agentic"),
                                  dtype="float32", num_layers=2)
        model = Model(cfg, attn_chunk=8, remat=False)
        params = model.init(jax.random.PRNGKey(0))

        def cycle(tp):
            eng = ServeEngine(model, params, num_pages=64, page_size=4,
                              max_pages_per_seq=16, tp=tp)
            session = BranchSession(eng, max_batch=8, seed=7)
            root = session.open([1, 2, 3, 4, 5], max_new_tokens=12)
            kids = session.branch(root, n=3)    # one fused CoW dispatch
            for hd in kids:
                session.resume(hd, greedy=False, temperature=2.0)
            session.wait(kids, produced=4)
            tails = [tuple(session.tokens(hd)) for hd in kids]
            session.commit(kids[1])
            out = session.finish(root)
            return tails, out, eng.cow_dispatches, session.tp

        one = cycle(1)
        two = cycle(2)
        assert one[3] == 1 and two[3] == 2
        assert one[:3] == two[:3], (one, two)
    """, n_devices=2)


def test_tp_engine_rejects_nondividing_mesh():
    run_in_subprocess("""
        import dataclasses, jax, pytest
        from repro.configs import get_config
        from repro.models.model import Model
        from repro.runtime.serve_loop import ServeEngine

        cfg = dataclasses.replace(get_config("paper-agentic"),
                                  dtype="float32", num_layers=2,
                                  num_heads=6, num_kv_heads=3,
                                  head_dim=32)
        model = Model(cfg, attn_chunk=8, remat=False)
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="num_kv_heads"):
            ServeEngine(model, params, num_pages=16, page_size=4, tp=2)
    """, n_devices=2)


def test_sanitize_drops_nondividing_axes():
    import jax

    from repro.configs import get_config
    from repro.distributed.mesh import ParallelPlan
    from repro.distributed.sharding import sanitize
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("model",))

    class FakePlan:
        mesh = type("M", (), {"shape": {"model": 16, "data": 16,
                                        "pod": 2}})()

    plan = FakePlan()
    # kv=8 cannot shard over model=16 -> dropped
    assert sanitize(plan, P(None, "model"), (28, 8)) == P(None, None)
    # heads=32 can
    assert sanitize(plan, P(None, "model"), (28, 32)) == P(None, "model")
    # tuple axes: ('pod','data') = 32 must divide the batch
    assert sanitize(plan, P(("pod", "data"), None), (128, 4)) == \
        P(("pod", "data"), None)
    assert sanitize(plan, P(("pod", "data"), None), (1, 4)) == P(None, None)
