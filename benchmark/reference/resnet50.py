"""Plain reference for the ``resnet50`` configuration: ResNet v1.5
(He et al. 2015, stride on the 3x3 of each bottleneck) forward, softmax
cross-entropy, gradients and SGD with momentum, in straightforward
``jax.numpy`` / ``jax.lax``: no scan, no fusion of steps, no donation.
It imports nothing of ``mxnet_tpu``; the parameter NAMES are the repo's
(``stage1_unit1_1_conv_weight``, ``..._bn_gamma``, ``fc1_weight``) so
that one seeded set of weights can be handed to both sides.

Departures from the paper, all the repo's own (``models/resnet.py``):
BatchNorm eps 2e-5, learnable gamma, biased batch variance; the loss is
the SUM of cross-entropies scaled by 1/batch in the optimizer
(``rescale_grad``); weight decay on ``*_weight`` and ``*_gamma`` only.

``dtype`` is the precision the forward and backward are computed in:
float32 runs under ``jax.default_matmul_precision("highest")`` (the
reference); bfloat16 is the control, the step a later PR is tempted to
take. Master weights and momentum stay float32 in both.
"""
import numpy as np

UNITS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
WIDTHS = (256, 512, 1024, 2048)
BN_EPS = 2e-5


def conv_table(cfg):
    """Every conv+BN pair in forward order:
    ``(name, cin, cout, kernel, stride, pad)``."""
    out = [("stem", int(cfg["image_shape"][0]), 64, 7, 2, 3)]
    cin = 64
    for stage, (n_units, width) in enumerate(
            zip(UNITS[int(cfg["num_layers"])], WIDTHS)):
        for unit in range(n_units):
            stride = 1 if (stage == 0 or unit > 0) else 2
            name = "stage%d_unit%d" % (stage + 1, unit + 1)
            out.append((name + "_1", cin, width // 4, 1, 1, 0))
            out.append((name + "_2", width // 4, width // 4, 3, stride, 1))
            out.append((name + "_3", width // 4, width, 1, 1, 0))
            if unit == 0:
                out.append((name + "_sc", cin, width, 1, stride, 0))
            cin = width
    return out


def param_shapes(cfg):
    """``(params, aux)`` name -> shape, in the repo's naming."""
    params, aux = {}, {}
    for name, cin, cout, k, _, _ in conv_table(cfg):
        params[name + "_conv_weight"] = (cout, cin, k, k)
        params[name + "_bn_gamma"] = (cout,)
        params[name + "_bn_beta"] = (cout,)
        aux[name + "_bn_moving_mean"] = (cout,)
        aux[name + "_bn_moving_var"] = (cout,)
    params["fc1_weight"] = (int(cfg["num_classes"]), WIDTHS[-1])
    params["fc1_bias"] = (int(cfg["num_classes"]),)
    return params, aux


def make_params(cfg, seed):
    """Seeded initial weights, made on the device in one jitted call:
    He-normal convolutions, N(0, 0.01) classifier, gamma near one (not
    all equal, so a gamma/beta mix-up shows), beta small."""
    import jax
    import jax.numpy as jnp
    pshapes, ashapes = param_shapes(cfg)
    names = sorted(pshapes)

    def init(key):
        out = {}
        for i, n in enumerate(names):
            k = jax.random.fold_in(key, i)
            shape = pshapes[n]
            if n.endswith("_conv_weight"):
                fan_in = shape[1] * shape[2] * shape[3]
                out[n] = jax.random.normal(k, shape, jnp.float32) \
                    * np.float32(np.sqrt(2.0 / fan_in))
            elif n.endswith("_gamma"):
                out[n] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif n.endswith("_beta") or n.endswith("_bias"):
                out[n] = 0.01 * jax.random.normal(k, shape, jnp.float32)
            else:
                out[n] = 0.01 * jax.random.normal(k, shape, jnp.float32)
        return out

    params = jax.jit(init)(jax.random.PRNGKey(int(seed) % (2 ** 31)))
    aux = {n: (jnp.ones(s, jnp.float32) if n.endswith("_var")
               else jnp.zeros(s, jnp.float32)) for n, s in ashapes.items()}
    return params, aux


def make_batches(cfg, seed, n, batch):
    """The seed's first ``n`` synthetic batches ``[(data, label)]``, made on
    the device in one jitted call: every row differs, every batch differs,
    and batch ``i`` is the same whatever ``n`` is. Labels are class ids as
    float32, the repo's label convention."""
    import jax
    import jax.numpy as jnp
    shape = (int(batch),) + tuple(int(d) for d in cfg["image_shape"])
    classes = int(cfg["num_classes"])

    def make(key):
        out = []
        for i in range(int(n)):
            k = jax.random.fold_in(key, 1000 + i)
            label = jax.random.randint(jax.random.fold_in(k, 1),
                                       (int(batch),), 0, classes)
            out.append((jax.random.normal(k, shape, jnp.float32),
                        label.astype(jnp.float32)))
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31)))


def flops_per_sample(cfg):
    """Forward + backward FLOPs one sample requires: 2 per multiply-add,
    forward once and backward twice (input and weight gradients), over the
    convolutions and the classifier. Elementwise work is left out."""
    table = {name: row for name, *row in conv_table(cfg)}

    def conv(name, side):
        cin, cout, k, stride, pad = table[name]
        out = (side + 2 * pad - k) // stride + 1
        return out, out * out * cin * cout * k * k

    side, macs = conv("stem", int(cfg["image_shape"][1]))
    side = (side + 2 - 3) // 2 + 1                # the 3x3/2 max-pool
    for stage, n_units in enumerate(UNITS[int(cfg["num_layers"])]):
        for unit in range(n_units):
            name = "stage%d_unit%d" % (stage + 1, unit + 1)
            if unit == 0:
                macs += conv(name + "_sc", side)[1]
            for part in ("_1", "_2", "_3"):
                side, m = conv(name + part, side)
                macs += m
    macs += WIDTHS[-1] * int(cfg["num_classes"])
    return 3 * 2 * macs


def _bn(x, gamma, beta):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    inv = jax.lax.rsqrt(var + jnp.asarray(BN_EPS, x.dtype))
    return (x - mean) * inv * gamma.reshape(1, -1, 1, 1) \
        + beta.reshape(1, -1, 1, 1)


def _conv_bn(p, x, name, stride, pad, act=True):
    import jax
    import jax.numpy as jnp
    y = jax.lax.conv_general_dilated(
        x, p[name + "_conv_weight"], (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=("NCHW", "OIHW", "NCHW"))
    y = _bn(y, p[name + "_bn_gamma"], p[name + "_bn_beta"])
    return jnp.maximum(y, 0) if act else y


def loss_sum(params, data, label, cfg, dtype):
    """Sum over rows of the softmax cross-entropy, training-mode BN."""
    import jax
    import jax.numpy as jnp
    p = {k: v.astype(dtype) for k, v in params.items()}

    def conv_bn(x, name, stride, pad, act=True):
        return _conv_bn(p, x, name, stride, pad, act)

    x = conv_bn(data.astype(dtype), "stem", 2, 3)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    for stage, n_units in enumerate(UNITS[int(cfg["num_layers"])]):
        for unit in range(n_units):
            stride = 1 if (stage == 0 or unit > 0) else 2
            name = "stage%d_unit%d" % (stage + 1, unit + 1)
            y = conv_bn(x, name + "_1", 1, 0)
            y = conv_bn(y, name + "_2", stride, 1)
            y = conv_bn(y, name + "_3", 1, 0, act=False)
            sc = x if unit > 0 else conv_bn(x, name + "_sc", stride, 0,
                                            act=False)
            x = jnp.maximum(y + sc, 0)
    x = jnp.mean(x, axis=(2, 3))
    logits = x @ p["fc1_weight"].T + p["fc1_bias"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rows = jnp.arange(logits.shape[0])
    return -jnp.sum(logp[rows, label.astype(jnp.int32)])


def learning_rate(fit, n):
    """The rate of update ``n`` (the first is 1): ``learning_rate``, reached
    by a linear warm-up from 0 over the first ``warmup_steps`` updates
    where ``fit`` has them (gradual warm-up, Goyal et al. 2017)."""
    lr, warm = float(fit["learning_rate"]), int(fit.get("warmup_steps", 0))
    return lr * min(1.0, n / warm) if warm else lr


def train_step(params, mom, data, label, cfg, fit, dtype="float32",
               rescale=None, precision="highest", lr=None):
    """One SGD-with-momentum step as ``fit`` states it. Returns
    ``(params, mom, mean loss)``. ``rescale`` defaults to 1/rows;
    ``precision`` None leaves float32 products at the device's default;
    ``lr`` (a scalar, may be traced) stands in for ``fit``'s rate."""
    import jax
    import jax.numpy as jnp
    import contextlib
    ctx = (jax.default_matmul_precision(precision)
           if str(dtype) == "float32" and precision
           else contextlib.nullcontext())
    with ctx:
        loss, grads = jax.value_and_grad(loss_sum)(
            params, data, label, cfg, jnp.dtype(dtype))
    rows = data.shape[0]
    rescale = (1.0 / rows) if rescale is None else rescale
    lr = float(fit["learning_rate"]) if lr is None else lr
    wd, mu = float(fit["wd"]), float(fit["momentum"])
    new_p, new_m = {}, {}
    for n, w in params.items():
        g = grads[n].astype(jnp.float32) * rescale
        wd_n = wd if (n.endswith("_weight") or n.endswith("_gamma")) else 0.0
        m = mu * mom[n] - lr * (g + wd_n * w)
        new_p[n], new_m[n] = w + m, m
    return new_p, new_m, loss / rows
