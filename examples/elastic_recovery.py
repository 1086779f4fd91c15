"""Fault tolerance end to end: train on a multi-device mesh, inject a
failure, restart from the async checkpoint onto a *smaller* (elastic) mesh,
and verify training continues with identical semantics.

Needs >1 device, so this example forces 8 host platform devices — run it
standalone (not under pytest):

    PYTHONPATH=src python examples/elastic_recovery.py
"""
import os
import tempfile

import jax

from repro.configs import smoke_config
from repro.launch.mesh import make_mesh, make_rules
from repro.launch.train import InjectedFailure, train


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    cfg = smoke_config("qwen2-7b").with_(n_layers=4, d_model=64, d_ff=128)

    with tempfile.TemporaryDirectory() as ckpt:
        mesh_a = make_mesh((4, 2), ("data", "model"))
        rules_a = make_rules(mesh_a)
        print(f"phase 1: training on mesh {dict(mesh_a.shape)} ...")
        try:
            train(cfg, steps=20, seq=32, global_batch=8, ckpt_dir=ckpt,
                  ckpt_every=5, mesh=mesh_a, rules=rules_a, fail_at_step=12,
                  seed=0)
        except InjectedFailure as e:
            print(f"  !! {e}")

        # half the fleet is gone: rebuild a 4-device mesh and resume
        mesh_b = make_mesh((2, 2), ("data", "model"))
        rules_b = make_rules(mesh_b)
        print(f"phase 2: elastic restart on mesh {dict(mesh_b.shape)} "
              f"(params re-sharded from the checkpoint manifest) ...")
        rep = train(cfg, steps=20, seq=32, global_batch=8, ckpt_dir=ckpt,
                    ckpt_every=5, mesh=mesh_b, rules=rules_b, seed=0)
        print(f"  resumed at step {20 - rep.steps_run}, finished at "
              f"{rep.final_step}; final loss {rep.losses[-1]:.4f}")
        print("elastic recovery OK")


if __name__ == "__main__":
    main()
