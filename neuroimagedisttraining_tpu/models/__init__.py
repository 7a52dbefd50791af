"""Model zoo registry.

Mirrors the reference's ``create_model`` switch
(``main_sailentgrads.py:164-178``: "3DCNN" -> AlexNet3D_Dropout, etc.) but
returns a flax module plus a uniform ``apply_fn(params, x, train, rng)``
closure that the vmapped trainer consumes.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax

from .alexnet3d import (
    AlexNet3D,
    AlexNet3DDeeper,
    AlexNet3DRegression,
    AlexNet3DS2D,
    SmallCNN3D,
    SmallCNN3DS2D,
)

ApplyFn = Callable[..., Any]


def _registry():
    from .resnet3d import (  # local import: keeps zoo modular
        ResNet3DL3,
        ResNet3DL3S2D,
    )
    from .resnet2d import ResNet18GN, TinyResNet18
    from .cnn2d import (
        CNNCifar10,
        CNNCifar100,
        CNNDropOut,
        CNNOriginalFedAvg,
        LeNet5,
        VGG11,
        VGG16,
    )
    from .meta import CNNCifar10Meta, MetaResNet20
    from .resnet_gn import resnet18_gn, resnet34_gn, resnet50_gn
    from .resnet2d import OriginalResNet18
    from .resnet_ip import ResNetIP
    from . import decoder

    def language_model(name):
        def build(num_classes, **kw):
            model = decoder.decoder(name, decoder.Share(**kw))
            if num_classes != model.num_classes:
                raise ValueError(
                    f"{name}: this share holds {model.num_classes} "
                    f"vocabulary rows, the data has {num_classes} ids")
            return model
        return build

    return {
        # the decoder family (models/decoder.py): a chip's share of a
        # published language model; kwargs are decoder.Share's fields
        **{name: language_model(name) for name in decoder.CONFIGS},
        # reference names (main_*.py --model flags)
        "3dcnn": lambda num_classes, **kw: AlexNet3D(num_classes=num_classes, **kw),
        # TPU-fast AlexNet3D over phase-decomposed input (ops/s2d.py);
        # same hypothesis class + outputs, input is (D', H', 8, W') phased
        "3dcnn_s2d": lambda num_classes, **kw: AlexNet3DS2D(num_classes=num_classes, **kw),
        "3dcnn_deeper": lambda num_classes, **kw: AlexNet3DDeeper(num_classes=num_classes, **kw),
        "3dcnn_regression": lambda num_classes, **kw: AlexNet3DRegression(
            num_outputs=num_classes, **kw
        ),
        "3dresnet": lambda num_classes, **kw: ResNet3DL3(num_classes=num_classes, **kw),
        # TPU-fast ResNet_l3 over phase-decomposed input (k3/p3 stem spec,
        # ops/s2d.py): the stem stage is 66% of the full-volume step (r4)
        "3dresnet_s2d": lambda num_classes, **kw: ResNet3DL3S2D(num_classes=num_classes, **kw),
        "resnet18": lambda num_classes, **kw: ResNet18GN(num_classes=num_classes, **kw),
        # BatchNorm variant (forward/eval parity; mutable batch_stats —
        # FL trainers use the GN twin, models/resnet2d.py docstring)
        "original_resnet18": lambda num_classes, **kw: OriginalResNet18(num_classes=num_classes, **kw),
        # research-leftover families (resnet_ip.py / resnet_meta*.py)
        "resnet_ip": lambda num_classes, **kw: ResNetIP(num_classes=num_classes, **kw),
        "resnet_meta": lambda num_classes, **kw: MetaResNet20(num_classes=num_classes, **kw),
        "tiny_resnet18": lambda num_classes, **kw: TinyResNet18(num_classes=num_classes, **kw),
        "cnn_cifar10": lambda num_classes, **kw: CNNCifar10(num_classes=num_classes, **kw),
        "cnn_cifar100": lambda num_classes, **kw: CNNCifar100(num_classes=num_classes, **kw),
        "cnn": lambda num_classes, **kw: CNNOriginalFedAvg(num_classes=num_classes, **kw),
        "lenet5": lambda num_classes, **kw: LeNet5(num_classes=num_classes, **kw),
        "vgg11": lambda num_classes, **kw: VGG11(num_classes=num_classes, **kw),
        "vgg16": lambda num_classes, **kw: VGG16(num_classes=num_classes, **kw),
        "cnn_dropout": lambda num_classes, **kw: CNNDropOut(num_classes=num_classes, **kw),
        "cnn_cifar10_meta": lambda num_classes, **kw: CNNCifar10Meta(num_classes=num_classes, **kw),
        "resnet18_gn": lambda num_classes, **kw: resnet18_gn(num_classes=num_classes, **kw),
        "resnet34_gn": lambda num_classes, **kw: resnet34_gn(num_classes=num_classes, **kw),
        "resnet50_gn": lambda num_classes, **kw: resnet50_gn(num_classes=num_classes, **kw),
        # CI/test model
        "small3dcnn": lambda num_classes, **kw: SmallCNN3D(num_classes=num_classes, **kw),
        # phased twin (k3/s2/p1 stem spec — ops/s2d.py)
        "small3dcnn_s2d": lambda num_classes, **kw: SmallCNN3DS2D(num_classes=num_classes, **kw),
    }


def create_model(name: str, num_classes: int = 1, **kwargs):
    reg = _registry()
    key = name.lower()
    if key not in reg:
        raise ValueError(f"unknown model {name!r}; available: {sorted(reg)}")
    return reg[key](num_classes, **kwargs)


def make_apply_fn(model, compute_dtype=None, channel_inject=False) -> ApplyFn:
    """Uniform apply closure: dropout rng threaded only in train mode.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) enables mixed precision the
    TPU way: master weights stay float32 in the optimizer, but params and
    inputs are cast on entry so every conv/matmul runs on the MXU in
    bfloat16 (~2.6x step throughput on AlexNet3D at full ABCD resolution);
    outputs are cast back to float32 so losses, gradients accumulated into
    the f32 masters, and eval metrics keep full precision. Leaves the model
    names in ``float32_leaves`` are not cast.

    ``channel_inject`` appends the trailing channel axis at apply time (the
    reference's per-batch ``x.unsqueeze(1)``, ``my_model_trainer.py:199``).
    Storing ABCD volumes channel-less matters on TPU: the last two dims of
    an array are tile-padded to (8,128)/(16,128), so a resident
    ``(..., 121, 1)`` cohort costs 8-16x its logical bytes in HBM, while
    ``(..., 145, 121)`` pads by ~1.1x; injecting onto the small gathered
    batch keeps the blowup off the big arrays.
    """
    import jax.numpy as jnp

    # leaves a model names as ``float32_leaves`` keep their type (the
    # decoder's selection bias: it decides a choice among float32 scores)
    kept = frozenset(getattr(model, "float32_leaves", ()))

    def _cast_in(tree):
        def cast(path, a):
            named = path and getattr(path[-1], "key", None) in kept
            if named or not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            return a.astype(compute_dtype)

        return jax.tree_util.tree_map_with_path(cast, tree)

    def _cast_out(tree):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            tree,
        )

    def apply_fn(params, x, train: bool, rng, mutable=False):
        """``mutable`` names flax collections to open for this call (what
        the model sows: the decoder's expert statistics); the result is then
        ``(out, collections)``."""
        if channel_inject:
            x = x[..., None]
        if compute_dtype is not None:
            params = _cast_in(params)
            x = _cast_in(x)     # floating inputs only: token ids stay ints
        out = model.apply(
            {"params": params}, x, train=train, mutable=mutable,
            **({"rngs": {"dropout": rng}} if train else {}))
        sown = None
        if mutable:
            out, sown = out
        if compute_dtype is not None:
            out = _cast_out(out)
        return (out, sown) if mutable else out

    return apply_fn


def init_params(model, rng: jax.Array, sample_shape: Tuple[int, ...],
                dtype="float32"):
    """Initialize parameters for inputs of ``sample_shape`` (without batch
    axis) and ``dtype``: float32 for volumes and images whatever they are
    stored as (the apply closure casts them), the data's own for integer
    inputs (token ids index an embedding).

    One jitted program: ``model.init`` runs the model's forward pass to learn
    the shapes, and eagerly that is every op of it at the sample's full size
    (125 programs and five live copies of the stem's conv output for
    AlexNet3D on a 121x145x121 volume: ~2 s of every start and the run's
    peak of device memory; my chip runs, PR 26). Under ``jit`` XLA drops the
    forward and keeps the initialisers; the parameters are the same bit for
    bit."""
    import jax.numpy as jnp

    def init(rng):
        x = jnp.zeros((1,) + tuple(sample_shape), dtype)
        variables = model.init({"params": rng, "dropout": rng}, x,
                               train=False)
        return variables["params"]

    return jax.jit(init)(rng)
