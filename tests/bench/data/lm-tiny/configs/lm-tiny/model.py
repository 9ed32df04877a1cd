"""The lm-tiny decoder as the program builds it, and its FLOP count."""
from __future__ import annotations


def arch(cfg: dict):
    """The program's ``ArchConfig`` of ``cfg``: its dense family, float32
    weights and compute, no rematerialization."""
    from repro.models.config import ArchConfig

    return ArchConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        d_head=cfg["head_dim"], rope_base=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["dtype"], compute_dtype=cfg["dtype"], remat=False)


def program_loss(cfg: dict):
    """``(loss_fn, init_fn)`` of ``repro.models.transformer.build_model``
    at ``cfg``'s sizes."""
    from repro.models.transformer import build_model

    bundle = build_model(arch(cfg))
    return bundle.loss, bundle.init


def forward_flops(cfg: dict, seq_len: int) -> int:
    """Forward FLOPs of one sequence of ``seq_len`` tokens: 2 x the
    multiply-adds of every projection (q, k, v, o, the MLP's three, the
    unembedding) at every position, and of the attention's scores and
    weighted values over the causal pairs (``seq_len (seq_len + 1) / 2`` a
    head). Norms, RoPE, softmax and the embedding lookup are not counted."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_token = (d * h * dh + 2 * d * kv * dh + h * dh * d
                 + 3 * d * cfg["intermediate_size"])
    pairs = seq_len * (seq_len + 1) // 2
    macs = cfg["num_hidden_layers"] * (seq_len * per_token + 2 * h * dh * pairs)
    macs += seq_len * d * cfg["vocab_size"]
    return 2 * macs
