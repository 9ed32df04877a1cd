"""The one traffic generator: a federation's data, split and batches from a seed.

A traffic mix is a JSON file in ``bench/traffic/`` (see ``full.json``); this
module reads its parameters and builds everything a run feeds the program.
What kind of data depends on the configuration's ``task``:

* classification (no ``task``): synthetic CIFAR-shaped data (a Gaussian
  mixture over class prototypes, flat ``[n, H*W*C]`` float32 rows) and the
  label-skewed split over groups and clients (Dirichlet, as in the paper's
  Sec. 5.1);
* ``causal_lm``: one token stream per client, of documents of
  heavy-tailed length (lognormal), each drawn from one domain's unigram
  distribution over the vocabulary and ended by an end-of-document id;
  the domain mixtures are skewed at both levels (``both_noniid``: each
  group's from Dir(alpha), each client's from Dir(alpha) around its
  group's), the two-level drift that MTGC corrects;
* which rows (or token windows) each client's packed shards hold, and which
  shard each group round draws.

``make_classification`` and ``partition`` are copies of
``repro.data.synthetic`` and ``repro.data.partition``, and the domains
follow ``repro.data.lm``, so a change to the program cannot change the
traffic. ``shard_rows``, ``token_windows`` and ``round_shards`` repeat the
draws of the program's packers (``pack_client_shards``, ``pack_lm_shards``)
and of its on-device batch selection (``select_round``), so that the plain
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


@dataclasses.dataclass
class TokenFederation:
    """One run's token data: each client's stream and the domain mixtures
    it was drawn from."""

    streams: list[list[np.ndarray]]  # [G][K] int32 token streams
    group_mix: np.ndarray            # [G, D] each group's domain mixture
    client_mix: np.ndarray           # [G, K, D] each client's


def make_federation(cfg: dict, traffic: dict,
                    seed: int) -> Federation | TokenFederation:
    """The data and split of one run of ``traffic`` on configuration ``cfg``."""
    if cfg.get("task") == "causal_lm":
        return make_token_federation(cfg, traffic, seed)
    G, K = cfg["levels"]
    n = traffic["samples_per_client"] * G * K
    dim = int(np.prod(cfg["image_shape"]))
    x, y = make_classification(np.random.default_rng(stream(seed, "data")),
                               n, cfg["num_classes"], dim, traffic["noise"])
    idx = partition(y, G, K, traffic["partition"], traffic["alpha"],
                    np.random.default_rng(stream(seed, "partition")),
                    traffic["min_per_client"])
    return Federation(x, y, idx)


def domain_mixtures(rng: np.random.Generator, groups: int, clients: int,
                    domains: int, alpha: float) -> tuple[np.ndarray,
                                                        np.ndarray]:
    """``(group_mix [G, D], client_mix [G, K, D])`` of a ``both_noniid``
    split: each group's mixture from Dir(alpha), each client's from
    Dir(alpha * D * its group's mixture), whose mean is the group's and
    whose concentration is the group level's. Parameters under 1e-3 are
    raised to 1e-3, so that the draw stays defined."""
    group = rng.dirichlet(alpha * np.ones(domains), size=groups)
    client = np.stack([
        rng.dirichlet(np.maximum(alpha * domains * gm, 1e-3), size=clients)
        for gm in group])
    return group, client


def document_stream(rng: np.random.Generator, protos: np.ndarray,
                    mix: np.ndarray, length: int, median: float,
                    sigma: float, eod: int) -> np.ndarray:
    """``length`` tokens of documents, each ended by ``eod``.

    A document's length (its ``eod`` included) is lognormal with median
    ``median`` and shape ``sigma``, at least 2; its domain is drawn from
    ``mix`` and its tokens from that domain's unigram row of ``protos``.
    The last document is cut where the stream ends.
    """
    lens, total = [], 0
    while total < length:
        n = max(2, int(round(rng.lognormal(np.log(median), sigma))))
        lens.append(n)
        total += n
    lens = np.asarray(lens)
    doms = rng.choice(len(mix), size=len(lens), p=mix)
    ends = np.cumsum(lens)
    owner = np.repeat(np.arange(len(lens)), lens)   # document of each slot
    out = np.empty(int(ends[-1]), np.int32)
    for d in np.unique(doms):
        slots = np.flatnonzero(doms[owner] == d)
        out[slots] = rng.choice(protos.shape[1], size=len(slots), p=protos[d])
    out[ends - 1] = eod
    return out[:length]


def make_token_federation(cfg: dict, traffic: dict,
                          seed: int) -> TokenFederation:
    """Per-client token streams of ``traffic`` on a ``causal_lm``
    configuration: ``tokens_per_client`` tokens each, over the
    configuration's ``vocab_size``, ended by its ``eos_token_id`` (the
    vocabulary's last id where it states none)."""
    G, K = cfg["levels"]
    vocab = cfg["vocab_size"]
    eod = int(cfg.get("eos_token_id", vocab - 1))
    if not 0 <= eod < vocab:
        raise ValueError(f"eos_token_id {eod} outside the vocabulary "
                         f"of {vocab}")
    if traffic["partition"] != "both_noniid":
        raise ValueError(f"unknown token partition {traffic['partition']!r}")
    data = np.random.default_rng(stream(seed, "data"))
    protos = data.dirichlet(0.05 * np.ones(vocab), size=traffic["domains"])
    group_mix, client_mix = domain_mixtures(
        np.random.default_rng(stream(seed, "partition")), G, K,
        traffic["domains"], traffic["alpha"])
    streams = [[document_stream(data, protos, client_mix[g, k],
                                traffic["tokens_per_client"],
                                traffic["doc_len_median"],
                                traffic["doc_len_sigma"], eod)
                for k in range(K)] for g in range(G)]
    return TokenFederation(streams, group_mix, client_mix)


def pack_rng(seed: int) -> np.random.Generator:
    """The generator handed to the program's packer (``pack_arrays`` or
    ``pack_tokens``)."""
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


def token_windows(streams: list[list[np.ndarray]], shards: int, steps: int,
                  batch: int, seq_len: int,
                  rng: np.random.Generator) -> dict[str, np.ndarray]:
    """``{"tokens", "targets"}``, ``[G, K, S, steps, B, seq_len]`` int32:
    the windows of every client's packed shards.

    The draw of the program's ``pack_lm_shards`` on per-client streams:
    clients in row-major order, each ``shards x steps x batch`` window
    starts uniform over its stream, the targets the tokens shifted by one.
    Given a generator in the state that ``pack_rng`` returns, it names the
    windows the packer put in each slot.
    """
    toks, targs = [], []
    for group in streams:
        tg, yg = [], []
        for s in group:
            starts = rng.integers(0, len(s) - seq_len - 1,
                                  size=(shards, steps, batch))
            win = starts[..., None] + np.arange(seq_len)
            tg.append(s[win].astype(np.int32))
            yg.append(s[win + 1].astype(np.int32))
        toks.append(tg)
        targs.append(yg)
    return {"tokens": np.asarray(toks), "targets": np.asarray(targs)}


def packed_slots(fed: Federation | TokenFederation, traffic: dict,
                 steps: int, rng: np.random.Generator
                 ) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """``(arrays, rows)``: ``arrays[name][rows]`` is what the program's
    packer put in each slot, ``rows`` being ``[G, K, S, steps, B]``.

    Classification: the federation's rows and ``shard_rows``' table.
    Tokens: ``token_windows``, one row per window.
    """
    if isinstance(fed, TokenFederation):
        win = token_windows(fed.streams, traffic["shards"], steps,
                            traffic["batch"], traffic["seq_len"], rng)
        lead = win["tokens"].shape[:5]
        rows = np.arange(int(np.prod(lead))).reshape(lead)
        return {k: v.reshape((-1,) + v.shape[5:]) for k, v in win.items()}, rows
    rows = shard_rows(fed.indices, traffic["shards"], steps, traffic["batch"],
                      rng)
    return {"x": fed.x, "y": fed.y}, rows


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


def round_batches(arrays: dict[str, np.ndarray], rows: np.ndarray,
                  sids: np.ndarray,
                  microbatches: int = 1) -> dict[str, np.ndarray]:
    """One global round's batches of every named array, ``[E, H, G, K, A,
    B, ...]``, on the host.

    ``arrays`` and ``rows`` are ``packed_slots``' and ``sids`` one round of
    ``round_shards``. A shard's ``steps = H * A`` step-batches are local
    step h's A microbatches in order, as ``select_round`` reads them.
    """
    G, K = rows.shape[:2]
    g = np.arange(G)[None, :, None]
    k = np.arange(K)[None, None, :]
    sel = rows[g, k, sids]                       # [E, G, K, H*A, B]
    E, _, _, steps, B = sel.shape
    sel = sel.reshape(E, G, K, steps // microbatches, microbatches, B)
    sel = np.moveaxis(sel, 3, 1)                 # [E, H, G, K, A, B]
    return {name: arr[sel] for name, arr in arrays.items()}
