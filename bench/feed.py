"""The one traffic generator: a federation's data, split and batches from a seed.

A traffic mix is a JSON file in ``bench/traffic/`` (see ``full.json``); this
module reads its parameters and builds everything a run feeds the program:

* synthetic CIFAR-shaped classification data (a Gaussian mixture over class
  prototypes, flat ``[n, H*W*C]`` float32 rows);
* the label-skewed split over groups and clients (Dirichlet, as in the
  paper's Sec. 5.1);
* which rows each client's packed shards hold, and which shard each group
  round draws.

``make_classification`` and ``partition`` are copies of
``repro.data.synthetic`` and ``repro.data.partition``, so a change to the
program cannot change the traffic. ``shard_rows`` and ``round_shards``
repeat the draws of the program's packer (``pack_client_shards``) and of
its on-device batch selection (``select_round``), so that the plain
reference sees the same batches as the program without reading anything the
program made.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

# One independent random stream per purpose, all from the run's --seed.
_STREAMS = {"data": 0, "partition": 1, "pack": 2, "weights": 3, "select": 4}


def stream(seed: int, name: str) -> np.random.SeedSequence:
    """The seed sequence of stream ``name``; any whole ``seed`` works."""
    return np.random.SeedSequence(entropy=int(seed) % 2**64,
                                  spawn_key=(_STREAMS[name],))


def jax_key(seed: int, name: str) -> jax.Array:
    """A JAX PRNG key for stream ``name``."""
    word = int(stream(seed, name).generate_state(1, np.uint32)[0]) >> 1
    return jax.random.PRNGKey(word)


def make_classification(rng: np.random.Generator, num_samples: int,
                        num_classes: int, dim: int,
                        noise: float = 1.0) -> tuple[np.ndarray, np.ndarray]:
    """Gaussian-mixture classification data: ``(x [n, dim] f32, y [n] i32)``.

    Each class has a random prototype; a sample is its class prototype plus
    isotropic noise.
    """
    protos = rng.standard_normal((num_classes, dim), dtype=np.float32)
    protos *= np.float32(2.0 / np.sqrt(dim) ** 0.5)
    y = rng.integers(0, num_classes, size=(num_samples,))
    x = rng.standard_normal((num_samples, dim), dtype=np.float32)
    x *= np.float32(noise)
    x += protos[y]
    return x, y.astype(np.int32)


def _dirichlet_split(rng, labels, num_parts, alpha, idx_pool):
    """Split ``idx_pool`` into ``num_parts`` label-skewed parts: each class's
    samples are divided with proportions drawn from Dir(alpha)."""
    parts = [[] for _ in range(num_parts)]
    for c in np.unique(labels[idx_pool]):
        idx_c = idx_pool[labels[idx_pool] == c]
        rng.shuffle(idx_c)
        props = rng.dirichlet(alpha * np.ones(num_parts))
        cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
        for p, chunk in enumerate(np.split(idx_c, cuts)):
            parts[p].extend(chunk.tolist())
    return [np.asarray(sorted(p), dtype=np.int64) for p in parts]


def _uniform_split(rng, num_parts, idx_pool):
    idx = idx_pool.copy()
    rng.shuffle(idx)
    return [np.asarray(sorted(c), dtype=np.int64)
            for c in np.array_split(idx, num_parts)]


def partition(labels: np.ndarray, num_groups: int, clients_per_group: int,
              mode: str, alpha: float, rng: np.random.Generator,
              min_per_client: int = 8) -> list[list[np.ndarray]]:
    """``indices[g][k]``: the sample indices of client k of group g.

    ``group_iid`` splits the data uniformly over groups and by Dirichlet
    over each group's clients; ``client_iid`` the other way round;
    ``both_noniid`` by Dirichlet at both levels. A split that leaves a
    client fewer than ``min_per_client`` samples is drawn again.
    """
    all_idx = np.arange(len(labels))
    for _attempt in range(50):
        if mode == "group_iid":
            groups = _uniform_split(rng, num_groups, all_idx)
            out = [_dirichlet_split(rng, labels, clients_per_group, alpha, g)
                   for g in groups]
        elif mode == "client_iid":
            groups = _dirichlet_split(rng, labels, num_groups, alpha, all_idx)
            out = [_uniform_split(rng, clients_per_group, g) for g in groups]
        elif mode == "both_noniid":
            groups = _dirichlet_split(rng, labels, num_groups, alpha, all_idx)
            out = [_dirichlet_split(rng, labels, clients_per_group, alpha, g)
                   for g in groups]
        else:
            raise ValueError(f"unknown partition mode {mode!r}")
        if min(len(c) for g in out for c in g) >= min_per_client:
            return out
    raise RuntimeError("could not draw a split with enough samples per client")


@dataclasses.dataclass
class Federation:
    """One run's data: rows, labels and each client's pool of row indices."""

    x: np.ndarray                  # [n, dim] float32
    y: np.ndarray                  # [n] int32
    indices: list[list[np.ndarray]]  # [G][K] row indices


def make_federation(cfg: dict, traffic: dict, seed: int) -> Federation:
    """The data and split of one run of ``traffic`` on configuration ``cfg``."""
    G, K = cfg["levels"]
    n = traffic["samples_per_client"] * G * K
    dim = int(np.prod(cfg["image_shape"]))
    x, y = make_classification(np.random.default_rng(stream(seed, "data")),
                               n, cfg["num_classes"], dim, traffic["noise"])
    idx = partition(y, G, K, traffic["partition"], traffic["alpha"],
                    np.random.default_rng(stream(seed, "partition")),
                    traffic["min_per_client"])
    return Federation(x, y, idx)


def pack_rng(seed: int) -> np.random.Generator:
    """The generator handed to the program's packer (``pack_arrays``)."""
    return np.random.default_rng(stream(seed, "pack"))


def shard_rows(indices: list[list[np.ndarray]], shards: int, steps: int,
               batch: int, rng: np.random.Generator) -> np.ndarray:
    """``[G, K, S, steps, B]`` row indices of every client's packed shards.

    The draw of the program's ``pack_client_shards``: clients in row-major
    order, each ``shards x steps x batch`` rows from its pool with
    replacement. Given a generator in the state that ``pack_rng`` returns,
    it names the rows the packer put in each slot.
    """
    return np.stack([np.stack([rng.choice(pool, size=(shards, steps, batch),
                                          replace=True) for pool in group])
                     for group in indices])


def round_shards(data_key: jax.Array, rounds: int, group_rounds: int,
                 groups: int, clients: int, shards: int) -> np.ndarray:
    """``[R, E, G, K]``: the shard each client's group round draws.

    The draw of the program's driver: per global round the selection key is
    split once (``key, rng = split(rng)``) and ``randint(key, (E, G, K), 0,
    S)`` picks the shards.
    """
    out, rng = [], data_key
    for _ in range(rounds):
        key, rng = jax.random.split(rng)
        out.append(np.asarray(jax.random.randint(
            key, (group_rounds, groups, clients), 0, shards)))
    return np.stack(out)


def round_batches(fed: Federation, rows: np.ndarray,
                  sids: np.ndarray) -> dict[str, np.ndarray]:
    """One global round's batches, ``[E, H, G, K, B, ...]``, on the host.

    ``rows`` is ``shard_rows``' table and ``sids`` one round of
    ``round_shards``.
    """
    G, K = rows.shape[:2]
    g = np.arange(G)[None, :, None]
    k = np.arange(K)[None, None, :]
    sel = rows[g, k, sids]                       # [E, G, K, H, B]
    sel = np.moveaxis(sel, 3, 1)                 # [E, H, G, K, B]
    return {"x": fed.x[sel], "y": fed.y[sel]}
