"""Traffic generator: one federated population and its image data.

A traffic mix (``bench/workloads/<traffic>.json``) fixes the federation:
how many clients, the Dirichlet concentration over labels, the cohort size,
the batch size, the local epochs, the stage held fixed, and a
``population_seed``.  The population seed fixes each client's size and
label mixture and the server's cohort draws, so every ``--seed`` runs the
same amount of work in the same rounds.  ``--seed`` draws what the work is
done on: the images, each client's batch order and the weights.

The partition is the label-Dirichlet split of Hsu et al. (2019), as
``repro.data.partition.dirichlet_partition`` does it; the images are class
prototypes plus noise, as ``repro.data.synthetic.make_image_dataset`` makes
them.  Both are copied here so that the yardstick stays put when the
program changes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

TRAFFIC_KEYS = ("n_clients", "alpha", "clients_per_round", "batch_size",
                "local_epochs", "stage", "population_seed")


@dataclasses.dataclass(frozen=True)
class Traffic:
    n_clients: int
    alpha: float
    clients_per_round: int
    batch_size: int
    local_epochs: int
    stage: int
    population_seed: int

    @classmethod
    def from_dict(cls, d: dict) -> "Traffic":
        missing = [k for k in TRAFFIC_KEYS if k not in d]
        if missing:
            raise ValueError(f"traffic file lacks {missing}")
        return cls(**{k: d[k] for k in TRAFFIC_KEYS})


def dirichlet_partition(rng: np.random.Generator, labels: np.ndarray,
                        n_clients: int, alpha: float,
                        min_samples: int = 2) -> list:
    """For each class, split its samples over the clients with proportions
    drawn from Dir(alpha).  Returns one sorted index array per client."""
    n_classes = int(labels.max()) + 1
    parts: list = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx_c = np.flatnonzero(labels == c)
        rng.shuffle(idx_c)
        props = rng.dirichlet(np.full(n_clients, alpha))
        cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
        for cid, part in enumerate(np.split(idx_c, cuts)):
            parts[cid].extend(part.tolist())
    out = []
    for cid in range(n_clients):
        idx = np.asarray(sorted(parts[cid]), np.int64)
        if len(idx) < min_samples:
            extra = rng.choice(len(labels), size=min_samples - len(idx),
                               replace=False)
            idx = np.sort(np.concatenate([idx, extra]))
        out.append(idx)
    return out


def population(traffic: Traffic, num_samples: int, num_classes: int):
    """(labels of the whole data set, per-client index arrays), both fixed
    by the traffic's population seed."""
    rng = np.random.default_rng(traffic.population_seed)
    labels = rng.integers(0, num_classes, num_samples).astype(np.int32)
    parts = dirichlet_partition(rng, labels, traffic.n_clients,
                                traffic.alpha)
    return labels, parts


def steps_per_client(parts, batch_size: int, local_epochs: int) -> list:
    """True local steps of each client in one round (``Batcher`` drops the
    remainder of an epoch and fills a client smaller than one batch)."""
    return [local_epochs * max(1, len(p) // batch_size) for p in parts]


def warm_step_counts(steps, cohort: int) -> list:
    """Every padded length E a cohort of ``cohort`` clients drawn from these
    can have: E is the cohort's largest step count, so it is one of the
    distinct counts at or above the ``cohort``-th smallest."""
    ordered = sorted(steps)
    floor = ordered[min(cohort, len(ordered)) - 1]
    return sorted({s for s in steps if s >= floor})


def _low_freq_prototype(rng, size: int, channels: int, cutoff: int = 4):
    cutoff = min(cutoff, size)
    spec = np.zeros((size, size, channels), np.complex64)
    spec[:cutoff, :cutoff] = (
        rng.standard_normal((cutoff, cutoff, channels))
        + 1j * rng.standard_normal((cutoff, cutoff, channels)))
    img = np.fft.ifft2(spec, axes=(0, 1)).real
    img = img / (np.abs(img).max() + 1e-6)
    return img.astype(np.float32)


def images(seed: int, labels: np.ndarray, num_classes: int, image_size: int,
           channels: int = 3, noise: float = 0.35) -> np.ndarray:
    """(N, H, W, C) float32 images of the given labels, drawn from ``seed``:
    a low-frequency prototype per class, Gaussian noise, and a texture per
    class scaled by one Gaussian draw per image."""
    rng = np.random.default_rng(seed)
    protos = np.stack([_low_freq_prototype(rng, image_size, channels)
                       for _ in range(num_classes)])
    tex = np.stack([_low_freq_prototype(rng, image_size, channels, cutoff=9)
                    for _ in range(num_classes)])
    n = len(labels)
    out = rng.standard_normal((n, image_size, image_size, channels),
                              dtype=np.float32)
    out *= np.float32(noise)
    out += protos[labels]
    out += (np.float32(0.5) * rng.standard_normal((n, 1, 1, 1),
                                                  dtype=np.float32)
            * tex[labels])
    return out
