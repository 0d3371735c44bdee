"""Multilayer perceptron classifier.

Reference: core/.../stages/impl/classification/OpMultilayerPerceptronClassifier.scala
(wraps Spark MLP: sigmoid hidden layers + softmax output, full-batch L-BFGS
over native BLAS). TPU-native: a jitted full-batch Adam loop (``lax.scan``)
over bf16-friendly matmuls; data-parallel scaling shards the batch over the
mesh 'data' axis and gradients reduce with psum (see parallel/).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .base import PredictorEstimator, PredictorModel


def _init_params(key, sizes: Sequence[int]):
    params = []
    for i in range(len(sizes) - 1):
        key, sub = jax.random.split(key)
        fan_in, fan_out = sizes[i], sizes[i + 1]
        w = jax.random.normal(sub, (fan_in, fan_out)) * jnp.sqrt(2.0 / fan_in)
        params.append({"w": w, "b": jnp.zeros(fan_out)})
    return params


def _matmul(h, layer, compute_dtype):
    """Layer matmul; with a low-precision compute dtype the operands ride
    the MXU in bf16 while accumulation and bias stay f32 (the standard TPU
    mixed-precision recipe — params and optimizer state remain f32)."""
    if compute_dtype is None:
        return h @ layer["w"] + layer["b"]
    dot = jax.lax.dot(
        h.astype(compute_dtype),
        layer["w"].astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    return dot + layer["b"]


def _forward(params, x, compute_dtype=None):
    h = x
    for layer in params[:-1]:
        # Spark MLP uses sigmoid hidden activations
        h = jax.nn.sigmoid(_matmul(h, layer, compute_dtype))
    return _matmul(h, params[-1], compute_dtype)


@partial(jax.jit, static_argnames=("sizes", "num_iters", "compute_dtype"))
def _train_mlp(x, y1h, row_mask, sizes, num_iters, step_size, seed,
               compute_dtype=None):
    cd = jnp.dtype(compute_dtype) if compute_dtype else None
    params = _init_params(jax.random.PRNGKey(seed), sizes)
    opt = optax.adam(step_size)
    opt_state = opt.init(params)
    n = jnp.maximum(row_mask.sum(), 1.0)

    def loss_fn(p):
        logits = _forward(p, x, cd)
        ll = optax.softmax_cross_entropy(logits, y1h) * row_mask
        return ll.sum() / n

    def step(carry, _):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = opt.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return (p, s), loss

    (params, _), losses = jax.lax.scan(step, (params, opt_state), None, length=num_iters)
    return params, losses


class MLPClassifierModel(PredictorModel):
    def __init__(self, params, num_classes: int, uid=None):
        super().__init__("mlp", uid=uid)
        # params stay DEVICE-resident (prediction runs there anyway);
        # persistence pulls them lazily via get_arrays
        self.params = list(params)
        self.num_classes = num_classes

    def get_arrays(self):
        out = {}
        for i, l in enumerate(self.params):
            out[f"w{i}"] = np.asarray(l["w"])
            out[f"b{i}"] = np.asarray(l["b"])
        return out

    def get_params(self):
        return {"num_classes": self.num_classes,
                "layer_sizes": [int(l["w"].shape[0]) for l in self.params]
                + [int(self.params[-1]["w"].shape[1])]}

    @classmethod
    def from_params(cls, params, arrays):
        layers = []
        i = 0
        while f"w{i}" in arrays:
            layers.append({"w": arrays[f"w{i}"], "b": arrays[f"b{i}"]})
            i += 1
        return cls(layers, params["num_classes"])

    def predict_arrays(self, x: np.ndarray):
        logits = np.asarray(_forward(self.params, jnp.asarray(x, dtype=jnp.float32)))
        logits64 = logits.astype(np.float64)
        shifted = logits64 - logits64.max(axis=1, keepdims=True)
        e = np.exp(shifted)
        prob = e / e.sum(axis=1, keepdims=True)
        return prob.argmax(axis=1).astype(np.float64), prob, logits64


class MLPClassifier(PredictorEstimator):
    """Spark MLP defaults: maxIter=100, stepSize=0.03 (we default Adam 1e-2),
    hidden layers user-specified (Spark requires explicit layers)."""

    model_type = "OpMultilayerPerceptronClassifier"

    def __init__(
        self,
        hidden_layers: Sequence[int] = (10,),
        max_iter: int = 100,
        step_size: float = 0.01,
        seed: int = 42,
        compute_dtype: str | None = None,
        uid: str | None = None,
    ):
        super().__init__("mlp", uid=uid)
        self.hidden_layers = tuple(hidden_layers)
        self.max_iter = max_iter
        self.step_size = step_size
        self.seed = seed
        #: e.g. "bfloat16": matmuls ride the MXU in bf16 with f32
        #: accumulation; params/optimizer state stay f32 (mixed precision)
        self.compute_dtype = compute_dtype

    def get_params(self):
        return {
            "hidden_layers": list(self.hidden_layers),
            "max_iter": self.max_iter,
            "step_size": self.step_size,
            "seed": self.seed,
            "compute_dtype": self.compute_dtype,
        }

    def fit_arrays(self, x, y, row_mask):
        from ..parallel.mesh import (
            data_row_multiple,
            pad_rows,
            shard_rows_if_active,
        )

        present = y[row_mask > 0]
        num_classes = max(int(present.max()) + 1 if len(present) else 2, 2)
        sizes = (x.shape[1], *self.hidden_layers, num_classes)
        # join the row-partitioned substrate (SURVEY §2.6): rows shard over
        # the ambient mesh's data axis; GSPMD propagates the sharding
        # through the scan body and psums the gradients over ICI. Mask-0
        # padding rows are inert (loss is mask-weighted, n = mask.sum()).
        # Device-resident inputs that need no padding stay on device — a
        # host pad of the wide bench's 512 MB x would round-trip it
        # through the host.
        mult = data_row_multiple()
        if x.shape[0] % mult:
            x, _ = pad_rows(np.asarray(x, dtype=np.float32), mult)
            y, _ = pad_rows(np.asarray(y, dtype=np.float32), mult)
            row_mask, _ = pad_rows(
                np.asarray(row_mask, dtype=np.float32), mult
            )
        y1h = jax.nn.one_hot(
            jnp.asarray(y).astype(jnp.int32), num_classes, dtype=jnp.float32
        )
        params, losses = _train_mlp(
            shard_rows_if_active(jnp.asarray(x, dtype=jnp.float32)),
            y1h,
            jnp.asarray(row_mask, dtype=jnp.float32),
            sizes,
            int(self.max_iter),
            float(self.step_size),
            int(self.seed),
            compute_dtype=self.compute_dtype,
        )
        self.metadata["finalLoss"] = float(np.asarray(losses)[-1])
        return MLPClassifierModel(params, num_classes)
