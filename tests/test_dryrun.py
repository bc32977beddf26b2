"""Distribution tests that need >1 device: run in a subprocess with
XLA_FLAGS set (the main pytest session keeps 1 device).  Also unit tests
for the HLO analysis (trip counts, replica groups, roofline math)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch import hlo_analysis as ha

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code: str, devices: int = 8) -> str:
    # the child runs on virtual CPU devices, never on an accelerator the
    # parent may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# HLO analysis unit tests
# ---------------------------------------------------------------------------

def test_shape_bytes():
    assert ha.shape_bytes("f32[128,1024]{1,0}") == 128 * 1024 * 4
    assert ha.shape_bytes("bf16[8]") == 16
    assert ha.shape_bytes("(f32[2,2], s32[4])") == 16 + 16
    assert ha.shape_bytes("f32[]") == 4  # scalar


def test_replica_group_parsing():
    g = ha.parse_replica_groups("replica_groups={{0,1},{2,3}}")
    assert g == [[0, 1], [2, 3]]
    g = ha.parse_replica_groups("replica_groups=[4,2]<=[8]")
    assert g == [[0, 1], [2, 3], [4, 5], [6, 7]]
    g = ha.parse_replica_groups("replica_groups=[2,4]<=[4,2]T(1,0)")
    assert g == [[0, 2, 4, 6], [1, 3, 5, 7]]


def test_classify_groups():
    # production coords: id = data*16 + model (single pod)
    assert ha.classify_group([0, 1, 2], multi_pod=False) == "intra_group"
    assert ha.classify_group([0, 16], multi_pod=False) == "intra_pod"
    assert ha.classify_group([0, 256], multi_pod=True) == "cross_pod"


def test_roofline_math():
    r = ha.roofline(flops_per_dev=197e12, bytes_per_dev=819e9,
                    coll_bytes_per_dev=0.0, model_flops_total=197e12 * 256,
                    chips=256)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.dominant in ("compute", "memory")
    assert r.useful_ratio == pytest.approx(1.0)
    assert r.roofline_fraction == pytest.approx(1.0)


def test_nested_while_trip_counts_subprocess():
    out = _run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import collective_bytes
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        W = jax.ShapeDtypeStruct((64, 64), jnp.float32,
                                 sharding=NamedSharding(mesh, P(None, "model")))
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data", None)))
        def f(x, w):
            def outer(c, _):
                def inner(c2, _):
                    return (c2 @ w) @ w.T, None
                c2, _ = jax.lax.scan(inner, c, None, length=5)
                return c2, None
            out, _ = jax.lax.scan(outer, x, None, length=3)
            return out.sum()
        c = jax.jit(f).lower(x, W).compile()
        stats = collective_bytes(c.as_text(), multi_pod=False)
        mults = sorted(d["mult"] for d in stats.details)
        print("MULTS", mults)
    """)
    assert "15.0" in out     # 3 (outer) x 5 (inner)


def test_tiny_cell_compiles_on_fake_mesh():
    """A reduced config passes the full run_cell machinery on 8 devices."""
    out = _run_sub("""
        import dataclasses, json
        import jax
        from repro.configs import REGISTRY, reduced_config
        from repro.configs.base import ShapeConfig
        from repro.launch import sharding as sh
        from repro.launch.inputs import input_specs
        from repro.launch.steps import make_train_step, make_serve_step
        from repro.models.params import abstract_params
        from repro.models import decode as dec
        from repro.optim.adamw import AdamWConfig, init_opt_state
        from jax.sharding import Mesh, NamedSharding
        import numpy as np

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        for name in ("llama3-8b", "mixtral-8x22b", "mamba2-780m",
                     "recurrentgemma-9b", "seamless-m4t-large-v2",
                     "qwen2-vl-2b"):
            cfg = dataclasses.replace(
                reduced_config(REGISTRY[name]), remat="full",
                d_model=64, param_dtype="bfloat16", compute_dtype="bfloat16")
            shape = ShapeConfig("t", "train", 32, 8)
            pspecs = sh.param_specs(cfg, mesh, fsdp=False)
            ap = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
                abstract_params(cfg), pspecs,
                is_leaf=lambda x: hasattr(x, "shape"))
            aopt = jax.eval_shape(init_opt_state, ap)
            ospecs = sh.opt_specs(cfg, mesh, pspecs)
            aopt = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
                aopt, ospecs, is_leaf=lambda x: hasattr(x, "shape"))
            batch = input_specs(cfg, shape, mesh)
            step = make_train_step(cfg, AdamWConfig(), microbatches=2)
            with mesh:
                c = jax.jit(step).lower(ap, aopt, batch).compile()
            assert c.memory_analysis().temp_size_in_bytes > 0
            # decode too
            dshape = ShapeConfig("d", "decode", 64, 8)
            ins = input_specs(cfg, dshape, mesh)
            sstep = make_serve_step(cfg)
            args = (ap, ins["cache"], ins["tokens"], ins["pos"])
            if "extras" in ins:
                jax.jit(sstep).lower(*args, ins["extras"]).compile()
            else:
                jax.jit(sstep).lower(*args).compile()
            print("OK", name)
    """, devices=8)
    assert out.count("OK") == 6


def test_trainer_midrun_relayout_meshswap_subprocess():
    """ROADMAP "trainer relayout on real fleets": with 8 forced host
    devices (== topology.total_chips) the adaptive controller moves
    spread_rate mid-training and ``Trainer._on_relayout`` performs an
    ACTUAL mesh swap — params/optimizer resharded onto the new mesh, the
    step re-jitted — and training keeps converging."""
    out = _run_sub("""
        import tempfile
        import jax
        import numpy as np
        from repro.configs import REGISTRY, reduced_config
        from repro.core.controller import ControllerConfig
        from repro.core.layout import Layout
        from repro.core.topology import ChipletTopology
        from repro.data.pipeline import (ShardedLoader, SyntheticCorpus,
                                         write_corpus_shards)
        from repro.runtime.trainer import Trainer, TrainerConfig

        topo = ChipletTopology(n_pods=1, groups_per_pod=4, chips_per_group=2)
        assert len(jax.devices()) == topo.total_chips == 8
        cfg = reduced_config(REGISTRY["llama3-8b"])
        tmp = tempfile.mkdtemp()
        corpus = SyntheticCorpus(cfg.vocab, seed=3)
        files = write_corpus_shards(tmp + "/data", corpus, n_shards=2,
                                    tokens_per_shard=20000)
        loader = ShardedLoader(files, seq_len=16, batch=8)
        mesh0 = Layout(topo, 1).make_mesh()        # s=1: data=4, model=2
        assert (mesh0.shape["data"], mesh0.shape["model"]) == (4, 2)
        tcfg = TrainerConfig(steps=6, ckpt_every=100, log_every=100,
                             ckpt_dir=tmp + "/ckpt")
        # threshold 0: every evaluation spreads -> s walks 1 -> 2 -> 4
        trainer = Trainer(cfg, mesh0, loader, tcfg, topology=topo,
                          controller_cfg=ControllerConfig(
                              scheduler_timer=2, threshold=0.0, min_dwell=0),
                          log=lambda s: None)
        out = trainer.run()
        assert out["counters"]["relayouts"] >= 2
        # the live mesh really swapped: s=4 -> one replica over all 8 chips
        assert (trainer.mesh.shape["data"], trainer.mesh.shape["model"]) \\
            == (1, 8)
        # params/optimizer migrated onto the new mesh
        for leaf in jax.tree.leaves(trainer.params):
            assert leaf.sharding.mesh.shape["model"] == 8
        for leaf in jax.tree.leaves(trainer.opt_state):
            if hasattr(leaf, "sharding"):
                assert leaf.sharding.mesh.shape["model"] == 8
        assert all(np.isfinite(l) for l in out["losses"])
        print("RELAYOUTS", int(out["counters"]["relayouts"]),
              "MESH", trainer.mesh.shape["data"], trainer.mesh.shape["model"])
    """)
    assert "RELAYOUTS" in out
    assert "MESH 1 8" in out


def test_dryrun_records_exist_or_skip():
    """If the full matrix has run, check record invariants."""
    d = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("dry-run matrix not yet generated")
    ok = skipped = 0
    for f in os.listdir(d):
        if not f.endswith(".json"):
            continue
        rec = json.load(open(os.path.join(d, f)))
        if rec["status"] == "skipped":
            skipped += 1
            assert "full quadratic attention" in rec["reason"]
        elif rec["status"] == "ok":
            ok += 1
            assert rec["memory"]["peak_per_device"] > 0
            if "roofline" in rec:
                r = rec["roofline"]
                assert r["compute_s"] > 0
                assert r["dominant"] in ("compute", "memory", "collective")
    assert ok + skipped >= 1
