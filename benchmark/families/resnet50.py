"""ResNet-50 v1 (He et al. 2015, Table 1, the 50-layer column) with
Nesterov momentum.

As in ``bert.py``: ``build`` hands the configuration to the program's zoo
model; weights, batches, FLOPs, bytes and the plain reference are the
benchmark's own. Two departures from the paper, both the program's and
followed here so that the two sides compute the same function: strided
windows pad as XLA's ``SAME`` does (one pixel less on the low side than the
paper's symmetric padding), and the stride of a down-sampling bottleneck
sits on its first 1x1 convolution (the original v1 placement). Batch norm's
running variance keeps the biased batch variance.
"""

import jax
import jax.numpy as jnp
import numpy as np

STAGES = [(3, 64, 256), (4, 128, 512), (6, 256, 1024), (3, 512, 2048)]


def build(config: dict, seed: int):
    from deeplearning4j_tpu.train.updaters import Nesterovs
    from deeplearning4j_tpu.zoo.resnet50 import ResNet50
    opt = config["optimizer"]
    return ResNet50(num_classes=config["num_classes"], height=config["image_size"],
                    width=config["image_size"], seed=seed % (2 ** 31),
                    updater=Nesterovs(opt["lr"], momentum=opt["momentum"])).init()


def blocks():
    """(name, c_in, mid, out, stride, projected) per bottleneck, in order."""
    c_in = 64
    for stage, (count, mid, out) in enumerate(STAGES):
        for block in range(count):
            yield f"s{stage}b{block}", c_in, mid, out, (2 if block == 0 and stage > 0 else 1), block == 0
            c_in = out


def convs(config: dict):
    """(name, its batch norm's name, k, c_in, c_out, output side) of every convolution."""
    side = -(-config["image_size"] // 2)
    yield "stem_conv", "stem_bn", 7, 3, 64, side
    side = -(-side // 2)  # the stem's 3x3/2 max pool
    for name, c_in, mid, out, stride, projected in blocks():
        side = -(-side // stride)
        yield f"{name}_c1", f"{name}_b1", 1, c_in, mid, side
        yield f"{name}_c2", f"{name}_b2", 3, mid, mid, side
        yield f"{name}_c3", f"{name}_b3", 1, mid, out, side
        if projected:
            yield f"{name}_sc", f"{name}_sb", 1, c_in, out, side


def init_params(config: dict, seed: int):
    """(params, model_state) in float32 on the device, one jitted call: He
    normal convolutions, N(0, 0.01) head, norms at (1, 0), running
    statistics at (0, 1)."""
    plan = list(convs(config))

    def make(key):
        keys = jax.random.split(key, len(plan) + 1)
        params, state = {}, {}
        for k, (name, bn, size, c_in, c_out, _) in zip(keys, plan):
            std = (2.0 / (size * size * c_in)) ** 0.5
            params[name] = {"W": std * jax.random.normal(k, (size, size, c_in, c_out), jnp.float32)}
            params[bn] = {"gamma": jnp.ones((c_out,), jnp.float32), "beta": jnp.zeros((c_out,), jnp.float32)}
            state[bn] = {"mean": jnp.zeros((c_out,), jnp.float32), "var": jnp.ones((c_out,), jnp.float32)}
        n = config["num_classes"]
        params["fc"] = {"W": 0.01 * jax.random.normal(keys[-1], (2048, n), jnp.float32),
                        "b": jnp.zeros((n,), jnp.float32)}
        return params, state

    return jax.jit(make)(jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 32)))


def batches(config: dict, traffic: dict, seed: int):
    """``count`` host batches of float32 NHWC images and one-hot labels; no mask."""
    rng = np.random.default_rng(seed)
    b, side, n = traffic["batch"], config["image_size"], config["num_classes"]
    eye = np.eye(n, dtype=np.float32)
    return [(rng.standard_normal((b, side, side, 3), dtype=np.float32),
             eye[rng.integers(0, n, (b,))], None) for _ in range(traffic["count"])]


def samples_per_step(traffic: dict) -> int:
    return traffic["batch"]


def flops_per_step(config: dict, traffic: dict) -> float:
    """3 x the forward's convolution and head FLOPs (2 per multiply-add);
    batch norm, pooling and the update count nothing."""
    forward = sum(2 * side * side * k * k * c_in * c_out for _, _, k, c_in, c_out, side in convs(config))
    return 3.0 * traffic["batch"] * (forward + 2 * 2048 * config["num_classes"])


def n_params(config: dict) -> int:
    return (sum(k * k * c_in * c_out + 2 * c_out for _, _, k, c_in, c_out, _ in convs(config))
            + 2048 * config["num_classes"] + config["num_classes"])


def least_bytes_per_step(config: dict, traffic: dict) -> float:
    """Parameters and the momentum trace read once and written once, plus
    the float32 batch in."""
    batch = traffic["batch"] * 4 * (config["image_size"] ** 2 * 3 + config["num_classes"])
    return 2.0 * 2 * 4 * n_params(config) + batch


def reference_loss(config: dict):
    """``loss_fn(params, state, batch, mm, conv)`` in float32; every
    bottleneck under ``jax.checkpoint`` so the backward pass at the timed
    size fits on the chip."""
    eps, decay = config["bn_eps"], config["bn_decay"]

    def norm(x, p, s):
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
        y = (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]
        return y, {"mean": decay * s["mean"] + (1 - decay) * mean, "var": decay * s["var"] + (1 - decay) * var}

    def loss_fn(params, state, batch, mm, conv):
        images, labels, _ = batch

        def conv_bn(x, new_state, c, b, stride, padding, relu):
            y, new_state[b] = norm(conv(x, params[c]["W"], stride, padding), params[b], state[b])
            return jax.nn.relu(y) if relu else y

        def bottleneck(x, name, stride, projected):
            local = {}
            y = conv_bn(x, local, f"{name}_c1", f"{name}_b1", stride, "VALID", True)
            y = conv_bn(y, local, f"{name}_c2", f"{name}_b2", 1, "SAME", True)
            y = conv_bn(y, local, f"{name}_c3", f"{name}_b3", 1, "VALID", False)
            if projected:
                x = conv_bn(x, local, f"{name}_sc", f"{name}_sb", stride, "VALID", False)
            return jax.nn.relu(y + x), local

        new_state = {}
        x = conv_bn(images, new_state, "stem_conv", "stem_bn", 2, "SAME", True)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
        for name, _, _, _, stride, projected in blocks():
            x, local = jax.checkpoint(bottleneck, static_argnums=(1, 2, 3))(x, name, stride, projected)
            new_state.update(local)
        logits = mm(jnp.mean(x, (1, 2)), params["fc"]["W"]) + params["fc"]["b"]
        loss = -jnp.mean(jnp.sum(labels * jax.nn.log_softmax(logits, -1), -1))
        return loss, new_state

    return loss_fn
