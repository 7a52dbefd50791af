from .types import FederatedData, pad_stack
from .synthetic import make_synthetic_federated
from .partition import (
    class_prior_partition,
    contiguous_reshard,
    dirichlet_partition,
    proportional_test_indices,
    record_data_stats,
    site_partition,
)
from .abcd import (
    load_abcd_h5,
    load_partition_data_abcd,
    load_partition_data_abcd_rescale,
    site_train_test_split,
    write_abcd_h5,
)
from .cifar import (
    load_partition_data_cifar,
    random_crop_flip,
)

# Dataset names (as dispatched below) whose loaders declare the reference's
# RandomCrop+flip train transform by setting FederatedData.aug_pad_value —
# the ONE source of truth for "is this dataset augmentable", used both by
# FedAlgorithm's auto-wiring input (via the loaded data's aug_pad_value)
# and by the runner's pre-load checkpoint-lineage guard. Keep in sync with
# the dispatch cases below.
AUGMENTABLE_DATASETS = (
    "cifar10", "cifar100", "tiny_imagenet", "tiny-imagenet-200", "tiny")


def dataset_is_augmentable(dataset: str) -> bool:
    return dataset.lower() in AUGMENTABLE_DATASETS


def load_federated_data(
    dataset: str,
    data_dir: str = "",
    client_number: int = 8,
    partition_method: str = "dir",
    partition_alpha: float = 0.3,
    val_fraction: float = 0.0,
    seed: int = 42,
    **kwargs,
) -> FederatedData:
    """Dataset dispatcher — the rebuild of each experiment main's
    ``load_data`` switch (``main_sailentgrads.py:130-161``)."""
    name = dataset.lower()
    if name in ("abcd", "abcd_rescale"):
        if name == "abcd" and not client_number:
            return load_partition_data_abcd(
                data_dir, val_fraction=val_fraction, **kwargs)
        return load_partition_data_abcd_rescale(
            data_dir, client_number, val_fraction=val_fraction, **kwargs)
    if name in ("abcd_site",):
        return load_partition_data_abcd(
            data_dir, val_fraction=val_fraction, **kwargs)
    if name in ("cifar10", "cifar100"):
        return load_partition_data_cifar(
            data_dir, dataset=name, partition_method=partition_method,
            partition_alpha=partition_alpha, client_number=client_number,
            val_fraction=val_fraction, seed=seed, **kwargs)
    if name in ("tiny_imagenet", "tiny-imagenet-200", "tiny"):
        from .tiny_imagenet import load_partition_data_tiny_imagenet

        return load_partition_data_tiny_imagenet(
            data_dir, partition_method=partition_method,
            partition_alpha=partition_alpha, client_number=client_number,
            val_fraction=val_fraction, seed=seed, **kwargs)
    if name in ("synthetic", "abcd_synth"):
        spc = kwargs.get("samples_per_client", 24)
        val_per_client = (
            max(1, int(val_fraction * spc)) if val_fraction > 0 else 0)
        return make_synthetic_federated(
            seed=seed, n_clients=client_number,
            val_per_client=val_per_client, **kwargs)
    if name == "token_shards":
        from .tokens import make_token_shards

        return make_token_shards(
            seed=seed, n_clients=client_number,
            train_per_client=kwargs.pop("samples_per_client", 4), **kwargs)
    raise ValueError(f"unknown dataset {dataset!r}")


__all__ = [
    "FederatedData",
    "pad_stack",
    "make_synthetic_federated",
    "load_federated_data",
    "class_prior_partition",
    "contiguous_reshard",
    "dirichlet_partition",
    "proportional_test_indices",
    "record_data_stats",
    "site_partition",
    "load_abcd_h5",
    "load_partition_data_abcd",
    "load_partition_data_abcd_rescale",
    "site_train_test_split",
    "write_abcd_h5",
    "load_partition_data_cifar",
    "random_crop_flip",
]
