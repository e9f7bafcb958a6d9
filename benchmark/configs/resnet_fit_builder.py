"""Builds the system under test for the ResNet ``entry: module_fit``
configurations: the repo's own ``models.resnet`` symbol."""


def build_symbol(cfg):
    from mxnet_tpu import models
    return models.resnet(
        num_classes=int(cfg["num_classes"]),
        num_layers=int(cfg["num_layers"]),
        image_shape=",".join(str(d) for d in cfg["image_shape"]))


def optimizer_params(mx, cfg):
    """``fit``'s optimizer arguments; with ``warmup_steps`` a scheduler of
    the user's own, as the Module API takes one: the rate rises linearly
    from 0 to ``learning_rate`` over the first ``warmup_steps`` updates."""
    fit = cfg["fit"]
    out = {"learning_rate": fit["learning_rate"],
           "momentum": fit["momentum"], "wd": fit["wd"]}
    warm = int(fit.get("warmup_steps", 0))
    if warm:
        class Warmup(mx.lr_scheduler.LRScheduler):
            def __call__(self, num_update):
                return self.base_lr * min(1.0, num_update / warm)

        out["lr_scheduler"] = Warmup()
    return out
