"""Manual data-parallel training with int8-compressed gradient all-reduce
over the DGRO ring (8 simulated hosts) — the distributed-optimization demo.

Must set the device-count flag before jax imports, so this example is its
own process:

    PYTHONPATH=src python examples/compressed_dp.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np                      # noqa: E402
import jax                              # noqa: E402
import jax.numpy as jnp                 # noqa: E402
from jax import shard_map  # noqa: E402
from repro.compat import make_mesh  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_arch      # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticLM  # noqa: E402
from repro.models import model as Mdl   # noqa: E402
from repro.train.collectives import compressed_grad_allreduce  # noqa: E402
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update  # noqa: E402
from repro.train.train_step import cross_entropy  # noqa: E402


def main():
    n_hosts = 8
    mesh = make_mesh((n_hosts,), ("data",))
    cfg = get_arch("musicgen-large").smoke()
    params = Mdl.init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=2e-3)
    opt = adamw_init(params)
    err = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                  global_batch=n_hosts * 2))

    def local_loss(p, batch):
        logits, _ = Mdl.forward(cfg, p, batch["tokens"], mode="train")
        loss, _ = cross_entropy(logits, batch["labels"])
        return loss

    def dp_step(p, opt, err, batch):
        """Runs per-host: local grads -> int8 ring all-reduce + error
        feedback -> identical AdamW update on every host."""
        loss, grads = jax.value_and_grad(local_loss)(p, batch)
        grads, new_err = compressed_grad_allreduce(grads, "data", err)
        new_p, new_opt, gnorm = adamw_update(opt_cfg, grads, opt, p)
        return new_p, new_opt, new_err, jax.lax.pmean(loss, "data"), gnorm

    step = shard_map(
        dp_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data")),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False)
    step = jax.jit(step, donate_argnums=(0, 1, 2))

    print(f"== compressed DP: {n_hosts} hosts, int8 ring all-reduce ==")
    for i in range(12):
        raw = data.batch(i)
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        params, opt, err, loss, gnorm = step(params, opt, err, batch)
        if i % 2 == 0:
            print(f"step {i:3d} loss {float(loss):7.4f} "
                  f"gnorm {float(gnorm):6.3f}")
    print("[example] OK: trained with 4x-compressed DCN gradient traffic")


if __name__ == "__main__":
    main()
