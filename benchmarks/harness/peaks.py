"""Published peaks, keyed by ``device_kind`` as JAX reports it, and the bytes
a decode step has to move. A device that is not in the table is an error."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: add it to "
                       "benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]


_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def param_count(c: dict) -> int:
    """Parameters of the dense RMSNorm / rotary GQA / SwiGLU decoder the
    configuration file describes (HF key names)."""
    e, m, v, nl = c["hidden_size"], c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]
    d = c.get("head_dim") or e // c["num_attention_heads"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    layer = e * hq * d * 2 + e * hkv * d * 2 + 3 * e * m + 2 * e
    head = 0 if c.get("tie_word_embeddings") else e * v
    return v * e + nl * layer + e + head


def kv_bytes_per_token(c: dict) -> int:
    d = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * d * _ITEM[c["torch_dtype"]]


def decode_step_bytes(c: dict, live_tokens: float, lanes: float) -> float:
    """Bytes ONE decode step must move, from shapes alone: every weight that
    takes part in a token's forward pass once (the embedding table is a
    gather of ``lanes`` rows, not a read of the table), the live KV of every
    lane read once, and one new KV row per lane written."""
    item = _ITEM[c["torch_dtype"]]
    embed_table = c["vocab_size"] * c["hidden_size"]
    weights = (param_count(c) - embed_table) * item + lanes * c["hidden_size"] * item
    return weights + (live_tokens + lanes) * kv_bytes_per_token(c)
