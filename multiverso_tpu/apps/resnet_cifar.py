"""ResNet-CIFAR data-parallel trainer (BASELINE config 5 analogue).

Reference workload: Torch fb.resnet ResNet-18 / Lasagne ResNet-32 on
CIFAR-10, data-parallel across Multiverso workers with all parameters in one
ArrayTable (ref: binding/lua/docs/BENCHMARK.md, binding/python/docs/
BENCHMARK.md — 4 workers ≈ 3.2-3.4x speedup). TPU-native shape:

* every parameter in one ArrayTable with the server-side **Adam** updater
* the batch sharded over the mesh (each shard = one reference "worker");
  XLA's sharding propagation inserts the gradient psum the PS Add used to
  carry over MPI
* the whole epoch is one jitted ``lax.scan`` — worker compute, gradient
  merge, and server update fuse into a single program per step
* BatchNorm running stats stay worker-local (the reference keeps BN local
  per GPU too) and ride the scan carry

Usage: ``python -m multiverso_tpu.apps.resnet_cifar -depth 20 -epochs 2``
(synthetic CIFAR unless ``-train_npz`` pointing at arrays is given).
"""

from __future__ import annotations

import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import multiverso_tpu as mv
from multiverso_tpu.models import resnet as resnet_lib
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import log


class ResNetTrainer:
    def __init__(self, depth: int = 20, num_classes: int = 10,
                 image_size: int = 32, batch_size: int = 128,
                 learning_rate: float = 1e-3, seed: int = 0):
        if not mv.Zoo.get().started:
            mv.init()
        self.batch_size = batch_size
        self.learning_rate = learning_rate
        params, bn = resnet_lib.init_resnet(
            jax.random.key(seed), depth=depth, num_classes=num_classes)
        flat, self._meta = resnet_lib.flatten_params(params)
        self.n_params = flat.size
        self.table = mv.ArrayTable(flat.size, updater="adam", init=flat,
                                   name=f"resnet{depth}_params")
        self.bn = bn
        self._mesh = mv.mesh()
        self._axis = mv.Zoo.get().shard_axis()

    def _shard_batches(self, x: np.ndarray, y: np.ndarray):
        b = self.batch_size
        n = (len(y) // b) * b
        xb = x[:n].reshape(-1, b, *x.shape[1:])
        yb = y[:n].reshape(-1, b)
        sharding = NamedSharding(self._mesh, P(None, self._axis))
        return (jax.device_put(jnp.asarray(xb),
                               NamedSharding(self._mesh,
                                             P(None, self._axis, None, None,
                                               None))),
                jax.device_put(jnp.asarray(yb), sharding))

    def _epoch_fn(self):
        if hasattr(self, "_epoch_jit"):
            return self._epoch_jit
        table, meta = self.table, self._meta
        opt = AddOption(learning_rate=self.learning_rate)

        def step(carry, batch):
            state, bn = carry
            x, y = batch
            flat = state["data"][: self.n_params]
            params = resnet_lib.unflatten_params(flat, meta)

            def lf(p):
                return resnet_lib.loss_fn(p, bn, x, y, train=True)

            (loss, new_bn), grads = jax.value_and_grad(lf, has_aux=True)(
                params)
            gflat, _ = jax.tree.flatten(grads)
            delta = jnp.concatenate([g.reshape(-1) for g in gflat])
            delta = jnp.zeros(table.padded_shape, table.dtype
                              ).at[: delta.size].set(delta)
            state = table.functional_add(state, delta, opt)
            return (state, new_bn), loss

        @jax.jit
        def epoch(state, bn, xb, yb):
            (state, bn), losses = jax.lax.scan(step, (state, bn), (xb, yb))
            return state, bn, losses

        self._epoch_jit = epoch
        return epoch

    def train(self, x: np.ndarray, y: np.ndarray,
              epochs: int = 1) -> Dict[str, float]:
        xb, yb = self._shard_batches(x, y)
        epoch = self._epoch_fn()
        state, bn = self.table.state, self.bn
        t0, losses = time.perf_counter(), None
        for _ in range(epochs):
            state, bn, losses = epoch(state, bn, xb, yb)
        # the readback waits for the whole epoch chain, so the clock
        # stops after the device has finished
        loss = float(jnp.mean(losses))
        dt = time.perf_counter() - t0
        self.table.adopt(state)
        self.bn = bn
        n = int(np.prod(yb.shape)) * epochs
        return {"loss": loss,
                "images_per_sec": n / dt, "seconds": dt,
                "sec_per_epoch": dt / epochs}

    def evaluate(self, x: np.ndarray, y: np.ndarray) -> float:
        params = resnet_lib.unflatten_params(
            self.table.get()[: self.n_params], self._meta)
        logits, _ = resnet_lib.apply_resnet(params, self.bn,
                                            jnp.asarray(x), train=False)
        return float(jnp.mean((jnp.argmax(logits, -1) == jnp.asarray(y))
                              .astype(jnp.float32)))


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    kw = {}
    i = 0
    while i < len(argv) - 1:
        if argv[i].startswith("-"):
            kw[argv[i].lstrip("-")] = argv[i + 1]
            i += 2
        else:
            i += 1
    depth = int(kw.get("depth", 20))
    epochs = int(kw.get("epochs", 1))
    batch = int(kw.get("batch_size", 128))
    n = int(kw.get("num_samples", 2048))
    mv.init()
    trainer = ResNetTrainer(depth=depth, batch_size=batch)
    x, y = resnet_lib.synthetic_cifar(n, seed=1)
    stats = trainer.train(x, y, epochs=epochs)
    log.info("resnet%d train: %s", depth, stats)
    xt, yt = resnet_lib.synthetic_cifar(512, seed=2)
    log.info("eval accuracy: %.4f", trainer.evaluate(xt, yt))
    mv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
