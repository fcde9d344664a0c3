"""The FusionAI showcase: decentralized pipeline training over a
heterogeneous consumer-GPU fleet — broker, DAG decomposition, scheduling,
FP/BP/Update execution with message passing, a mid-training node failure
with backup-pool replacement, and the TPU-native SPMD pipeline mapping.

    PYTHONPATH=src python examples/decentralized_pipeline.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core.broker import Broker
from repro.core.dag import build_model_dag
from repro.core.decomposer import decompose_contiguous
from repro.core.executor import LocalCluster, spmd_pipeline
from repro.core.perfmodel import LINK_REGIMES, PerfModel, make_fleet
from repro.core.pipeline import estimate_system
from repro.data.synthetic import SyntheticConfig, SyntheticLM


def main():
    cfg = dataclasses.replace(get_smoke_config("gpt3-24l"), vocab_size=256)
    B, S = 4, 32
    dag = build_model_dag(cfg, batch=B, seq=S, kind="train")
    print(f"IR plane: {len(dag)} ops, {dag.total_flops()/1e9:.2f} GFLOP/step, "
          f"{dag.total_param_bytes()/1e6:.1f} MB params")

    # --- broker: register a heterogeneous fleet, schedule the job --------
    broker = Broker(backup_fraction=0.25, seed=0)
    fleet = make_fleet([("rtx3080", 4), ("rtx4090", 2), ("rtx4080", 2)],
                       LINK_REGIMES["wan_1gbps"])
    for node in fleet:
        node.reliability = 0.98
        broker.register(node)
    sched = broker.submit_job(dag, n_parts=3)
    print(f"broker: {len(broker.active)} active + {len(broker.backup)} backup"
          f" nodes; schedule makespan {sched.makespan*1e3:.1f} ms "
          f"(feasible={sched.feasible})")

    # --- execution plane: pipeline-parallel FP/BP/Update ------------------
    parts = decompose_contiguous(dag, 3)
    cluster = LocalCluster(dag, parts, cfg, jax.random.PRNGKey(0))
    lm = SyntheticLM(SyntheticConfig(cfg.vocab_size, S, B, noise=0.05))
    print("decentralized training (3 compnodes):")
    for step in range(8):
        batch = lm.batch(step)
        loss = cluster.train_step(batch["tokens"], batch["labels"], lr=3e-3)
        if step % 2 == 0:
            print(f"  step {step}: loss {loss:.4f}  "
                  f"(bus traffic {cluster.bus.total_bytes/1e6:.2f} MB)")

    # --- fault tolerance: kill a node mid-job, draft a backup -------------
    victim = sched.assignment[0]
    print(f"simulating failure of compnode {victim} ...")
    broker.quit(victim, graceful=False)
    repl = [e for e in broker.events if e.kind == "replace"]
    print(f"  broker drafted replacement: {repl[-1].detail if repl else 'n/a'}")
    assert all(nid in broker.active
               for nid in broker.schedule.assignment.values())
    print("  all tasks remapped to online nodes ✓")

    # --- analytic estimate for this exact job (§4) ------------------------
    pm = PerfModel(fleet)
    est = estimate_system(dag, pm, [n.node_id for n in fleet[:3]],
                          n_batches=64, batch_size=B)
    print(f"analytic: latency {est['latency_s']*1e3:.1f} ms, pipelined x64 "
          f"batches {est['pipelined_s_eq4']:.2f} s, bubble "
          f"{est['bubble_fraction']*100:.0f}%")

    # --- production mapping: shard_map pipeline over 4 host devices ------
    n_dev = len(jax.devices())
    if n_dev >= 4:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(4)
        d = 32
        stage_w = jax.random.normal(jax.random.PRNGKey(1), (4, d, d)) * 0.2
        xs = jax.random.normal(jax.random.PRNGKey(2), (8, B, d))
        out = spmd_pipeline(lambda w, x: jnp.tanh(x @ w), stage_w, xs, mesh,
                            axis="stage")
        ref = xs
        for i in range(4):
            ref = jnp.tanh(ref @ stage_w[i])
        err = float(jnp.abs(out - ref).max())
        print(f"spmd_pipeline over {n_dev} devices "
              f"(collective_permute GPipe): max err vs sequential {err:.2e}")
    else:
        print(f"spmd_pipeline skipped: needs 4 devices, found {n_dev} "
              f"(chip_smoke.py --four-chips runs it on four TPUs)")


if __name__ == "__main__":
    main()
