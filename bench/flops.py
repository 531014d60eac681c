"""Operations and bytes that the algorithm needs, from shapes alone.

Counted at the configuration's dtypes (bfloat16 activations, keys and
values at the unpadded head dim), never from what an implementation
moves: the program's float32 pools, its 128-lane row padding and any
later change to them all read against the same yardstick.  Each term is
counted on its own:

  * matmul parameters touched: 2 operations per parameter per token (of
    a routed mixture of experts, only the experts a token is routed to,
    at the chip's share);
  * MiTA landmark scores: a closing window scores its landmark query
    against every key before its end, and takes the softmax-weighted sum
    of their values (per KV head);
  * the routed expert's k rows and the local window, per query head;
  * K and V as bfloat16 at the unpadded head dim.

Bytes are the least a kernel must move: what it reads once and writes
once, with one routed expert per KV head (query heads of a group that
route alike share its rows), so a roofline share from them cannot pass
100% unless the kernel's time leaves out part of its work.

What differs between architectures (the layers' matrix products, which
layers are MiTA layers) is counted by the configuration's model module
(`bench.model.module`): `token_flops`, `paged_decode` and `chunk_prefill`
here hand each call on to it.  The MiTA attention terms below are one
layer's and shared by every model module.
"""

from __future__ import annotations

from bench import model

BF16 = 2


def token_flops(spec: dict, pos: int, prompt: bool, head: bool) -> int:
    """Model operations for one token through every layer (and the head
    when its next token is sampled)."""
    return model.module(spec).token_flops(spec, pos, prompt, head)


def paged_decode(spec: dict, positions) -> tuple[int, int]:
    """(operations, bytes) of the paged decode kernel for one decode step
    over every layer, for the active slots at ``positions``."""
    return model.module(spec).paged_decode(spec, positions)


def chunk_prefill(spec: dict, rows) -> tuple[int, int]:
    """(operations, bytes) of the chunk-prefill kernel for one dispatch
    over every layer.  ``rows``: (resume point, tokens) per prefilled
    row."""
    return model.module(spec).chunk_prefill(spec, rows)


# ---------------------------------------------- one MiTA layer's terms --

def dims(spec: dict) -> dict:
    m = spec["mita"]
    return {"h": spec["num_attention_heads"],
            "kv": spec["num_key_value_heads"], "dh": spec["head_dim"],
            "w": m["window"], "k": m["expert_width"]}


def attn_matmul_params(spec: dict) -> int:
    """Parameters of one layer's attention projections (q, k, v, o)."""
    c = dims(spec)
    d = spec["hidden_size"]
    return d * (c["h"] + 2 * c["kv"]) * c["dh"] + c["h"] * c["dh"] * d


def visible_landmarks(pos: int, w: int, prompt: bool) -> int:
    """Landmarks a query at ``pos`` attends: windows closed by pos + 1
    inside the prompt, by pos for a generated token."""
    return (pos + 1) // w if prompt else pos // w


def attend_flops(spec: dict, pos: int, prompt: bool) -> int:
    """One layer's attention for one token, all query heads: landmark
    scores and values, the routed expert's k rows, the local window."""
    c = dims(spec)
    a = visible_landmarks(pos, c["w"], prompt)
    keys = a + (c["k"] if a else 0) + pos % c["w"] + 1
    return c["h"] * 4 * c["dh"] * keys


def landmark_flops(spec: dict, pos: int) -> int:
    """One layer's landmark build when ``pos`` closes a window: scores of
    every key before the window's end, and the weighted sum of values,
    per KV head (0 for other positions)."""
    c = dims(spec)
    if (pos + 1) % c["w"]:
        return 0
    return c["kv"] * 4 * c["dh"] * (pos + 1)


def paged_decode_layer(spec: dict, positions) -> tuple[int, int]:
    """(operations, bytes) of the paged decode kernel in one layer:
    attend + append for each active slot at its position.  Bytes: the
    visible landmark queries and values, one expert's k rows and the
    local window's rows (K and V) per KV head, the query and output per
    query head, and the appended K and V row."""
    c = dims(spec)
    ops = nbytes = 0
    for p in positions:
        p = int(p)
        a = visible_landmarks(p, c["w"], prompt=False)
        rows = (c["k"] if a else 0) + p % c["w"]   # read (new row is not)
        ops += attend_flops(spec, p, prompt=False)
        nbytes += BF16 * c["dh"] * (
            c["kv"] * (2 * a + 2 * rows + 2) + 2 * c["h"])
    return ops, nbytes


def chunk_prefill_layer(spec: dict, rows) -> tuple[int, int]:
    """(operations, bytes) of the chunk-prefill kernel in one layer.
    Operations: every token's attention at prompt semantics and the
    landmarks of the windows the chunk closes.  Bytes: the context's K
    and V before the chunk read once (the closing windows score every
    earlier key), the chunk's K and V written, its queries read and
    outputs written."""
    c = dims(spec)
    ops = nbytes = 0
    for t0, n in rows:
        t0, n = int(t0), int(n)
        for p in range(t0, t0 + n):
            ops += attend_flops(spec, p, prompt=True) + landmark_flops(
                spec, p)
        nbytes += BF16 * c["dh"] * (2 * c["kv"] * (t0 + n)
                                    + 2 * c["h"] * n)
    return ops, nbytes


def roofline_seconds(ops: int, nbytes: int, peak: dict) -> float:
    """Least time on the chip: the larger of the compute and the memory
    bound."""
    return max(ops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
