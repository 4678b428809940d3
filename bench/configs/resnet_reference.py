"""Plain reference of the training step that the ResNet configurations
state: bottleneck ResNet forward and loss, its gradient, the f16
all-reduce of the gradient over data-parallel workers, and the RMSprop
warm-up update with the slow-start learning rate (Akiba et al. 2017,
Appendix A).

Written from the published description and the configuration file, in
plain ``jax.numpy``/``jax.lax``. It imports nothing of the program. The
conventions the program fixes where the paper leaves a choice (stride on
the 3x3 conv, SAME padding, per-worker BN without moving averages) are
stated in the configuration file and followed here.

``dtype`` is the precision everything is held and computed in: float32
for the reference, bfloat16 for the control that has to fail.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Spec = List[Tuple[str, Tuple[int, ...]]]


def param_spec(model: Dict) -> Spec:
    """(path, shape) of every parameter, in a fixed order."""
    w = model["conv_width"]
    exp = model["bottleneck_expansion"]
    spec: Spec = [("stem/conv", (7, 7, model["image_channels"], w)),
                  ("stem/bn/scale", (w,)), ("stem/bn/bias", (w,))]
    c_in = w
    for si, blocks in enumerate(model["conv_stages"]):
        mid = w * 2 ** si
        c_out = mid * exp
        for bi in range(blocks):
            pre = f"stage{si}/block{bi}"
            spec += [(f"{pre}/conv1", (1, 1, c_in, mid)),
                     (f"{pre}/bn1/scale", (mid,)), (f"{pre}/bn1/bias", (mid,)),
                     (f"{pre}/conv2", (3, 3, mid, mid)),
                     (f"{pre}/bn2/scale", (mid,)), (f"{pre}/bn2/bias", (mid,)),
                     (f"{pre}/conv3", (1, 1, mid, c_out)),
                     (f"{pre}/bn3/scale", (c_out,)),
                     (f"{pre}/bn3/bias", (c_out,))]
            if bi == 0:
                spec += [(f"{pre}/proj", (1, 1, c_in, c_out)),
                         (f"{pre}/proj_bn/scale", (c_out,)),
                         (f"{pre}/proj_bn/bias", (c_out,))]
            c_in = c_out
    spec += [("fc/w", (c_in, model["num_classes"])),
             ("fc/b", (model["num_classes"],))]
    return spec


def bn_sites(spec: Spec) -> List[str]:
    """Names of the BN sites, in parameter order."""
    return [p[:-len("/scale")] for p, _ in spec if p.endswith("/scale")]


def init_leaf(key, index: int, path: str, shape, dtype=jnp.float32):
    """Leaf ``index`` of the spec, drawn from ``key``: He-normal convs,
    fan-in-normal FC weights, BN scale 1, biases 0."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name in ("bias", "b"):
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, index)
    if len(shape) == 4:
        fan_in = shape[0] * shape[1] * shape[2]
        std = math.sqrt(2.0 / fan_in)
    else:
        std = 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def init_params(key, spec: Spec, dtype=jnp.float32) -> Dict[str, jax.Array]:
    return {p: init_leaf(key, i, p, s, dtype)
            for i, (p, s) in enumerate(spec)}


# ---------------------------------------------------------------- input

def augment_params(seed: int, step, total: int, max_shift: int):
    """Per-image [flip, dy, dx, 0] for one step: a threefry draw keyed by
    ``fold_in(PRNGKey(seed), step)``, the derivation the program states
    for its input augmentation (flip with probability 1/2, shifts
    uniform in [-max_shift, max_shift])."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    kf, ks = jax.random.split(key)
    flip = jax.random.bernoulli(kf, 0.5, (total,)).astype(jnp.int32)
    shifts = jax.random.randint(ks, (total, 2), -max_shift, max_shift + 1,
                                dtype=jnp.int32)
    return jnp.concatenate([flip[:, None], shifts,
                            jnp.zeros((total, 1), jnp.int32)], axis=1)


def augment(images, params, mean, std):
    """Horizontal flip, cyclic shift by (dy, dx), then per-channel
    ``(x - mean) / std``."""
    def one(img, p):
        img = jnp.where(p[0] > 0, img[:, ::-1, :], img)
        return jnp.roll(img, (p[1], p[2]), axis=(0, 1))
    x = jax.vmap(one)(images.astype(jnp.float32), params)
    mean = jnp.asarray(mean, jnp.float32)
    return (x - mean) * (1.0 / jnp.asarray(std, jnp.float32))


# -------------------------------------------------------------- forward

def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, scale, bias, eps):
    axes = (0, 1, 2)
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    y = (x - mean) / jnp.sqrt(var + eps) * scale + bias
    return y, (mean, var)


def forward(params, images, model: Dict, eps: float):
    """Logits, and the batch statistics (mean, var) of every BN site by
    its name, for one worker's rows."""
    stats = {}

    def bn(x, site, relu=True):
        y, st = _bn(x, params[f"{site}/scale"], params[f"{site}/bias"], eps)
        stats[site] = st
        return jax.nn.relu(y) if relu else y

    x = _conv(images, params["stem/conv"], 2)
    x = bn(x, "stem/bn")
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si, blocks in enumerate(model["conv_stages"]):
        for bi in range(blocks):
            pre = f"stage{si}/block{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            if bi == 0:
                sc = bn(_conv(x, params[f"{pre}/proj"], stride),
                        f"{pre}/proj_bn", relu=False)
            else:
                sc = x
            y = bn(_conv(x, params[f"{pre}/conv1"]), f"{pre}/bn1")
            y = bn(_conv(y, params[f"{pre}/conv2"], stride), f"{pre}/bn2")
            y = bn(_conv(y, params[f"{pre}/conv3"]), f"{pre}/bn3",
                   relu=False)
            x = jax.nn.relu(y + sc)
    x = jnp.mean(x, axis=(1, 2))
    logits = x @ params["fc/w"] + params["fc/b"]
    return logits, stats


def worker_loss(params, images, labels, model: Dict, eps: float):
    logits, stats = forward(params, images, model, eps)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), stats


# ------------------------------------------------------------ optimizer

def schedule(recipe: Dict, step, global_batch: int):
    """(eta, alpha_sgd) at ``step``: slow-start learning rate (0.5x the
    linear-scaling rate for the first 40 epochs, then 0.075x, 0.01x,
    0.001x) and the ELU-shaped RMSprop-to-SGD transition."""
    spe = -(-recipe["train_images"] // global_batch)
    epoch = jnp.asarray(step, jnp.float32) / spe
    base = recipe["base_lr_per_256"] * global_batch / 256.0
    eta = base * jnp.where(epoch < 40.0, 0.5, jnp.where(
        epoch < 70.0, 0.075, jnp.where(epoch < 85.0, 0.01, 0.001)))
    c, p = recipe["beta_center"], recipe["beta_period"]
    a = jnp.where(epoch < c, 0.5 * jnp.exp(2.0 * (epoch - c) / p),
                  0.5 + 2.0 * (epoch - c) / p)
    return eta, jnp.minimum(a, 1.0)


def decays(path: str, recipe: Dict) -> bool:
    return not any(part in recipe["no_decay"] for part in path.split("/"))


def update(params, delta, m, grads, step, recipe: Dict, global_batch: int):
    """Hybrid RMSprop warm-up (paper A.1) with L2 weight decay:
    m = mu2 m + (1-mu2) g^2; delta = mu1 delta - (a_sgd + a_rms /
    (sqrt(m) + eps)) g; theta += eta delta; a_rms = (1 - a_sgd)
    eta_rmsprop / eta."""
    eta, a_sgd = schedule(recipe, step, global_batch)
    a_rms = (1.0 - a_sgd) * recipe["eta_rmsprop"] / eta
    new_p, new_d, new_m = {}, {}, {}
    for path, theta in params.items():
        dt = theta.dtype
        g = grads[path]
        if decays(path, recipe):
            g = g + recipe["weight_decay"] * theta
        mm = recipe["mu2"] * m[path] + (1.0 - recipe["mu2"]) * g * g
        coef = a_sgd + a_rms / (jnp.sqrt(mm) + recipe["eps"])
        d = recipe["mu1"] * delta[path] - coef * g
        new_p[path] = (theta + eta * d).astype(dt)
        new_d[path] = d.astype(dt)
        new_m[path] = mm.astype(dt)
    return new_p, new_d, new_m


# ----------------------------------------------------------------- step

def wire_mean(grads, wire_dtype, dtype):
    """Mean over the leading worker axis of gradients sent in
    ``wire_dtype``: each worker's gradient is rounded to the wire type,
    the sum is rounded to it again, and the mean is taken after the cast
    back to ``dtype``."""
    n = next(iter(grads.values())).shape[0]
    out = {}
    for path, g in grads.items():
        s = jnp.sum(g.astype(wire_dtype).astype(jnp.float32), axis=0)
        out[path] = s.astype(wire_dtype).astype(dtype) / n
    return out


def train_step(params, delta, m, step, images, labels, *, model: Dict,
               recipe: Dict, eps: float, global_batch: int, n_workers: int,
               wire_dtype=jnp.float16):
    """One data-parallel step. ``images``/``labels`` hold the whole
    global batch, worker ``w`` taking rows ``[w*b, (w+1)*b)``; the
    workers run one after another. Returns the new (params, delta, m),
    the mean of the workers' losses, the synced gradient, and the BN
    statistics with a leading worker axis."""
    dtype = next(iter(params.values())).dtype
    b = images.shape[0] // n_workers
    x = images.astype(dtype).reshape((n_workers, b) + images.shape[1:])
    y = labels.reshape(n_workers, b)

    def one(xy):
        (loss, st), g = jax.value_and_grad(worker_loss, has_aux=True)(
            params, xy[0], xy[1], model, eps)
        return loss.astype(jnp.float32), st, g

    losses, stats, grads = jax.lax.map(one, (x, y))
    synced = wire_mean(grads, wire_dtype, dtype)
    new_p, new_d, new_m = update(params, delta, m, synced, step, recipe,
                                 global_batch)
    return new_p, new_d, new_m, jnp.mean(losses), synced, stats
