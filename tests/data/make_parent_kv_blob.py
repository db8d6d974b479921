"""Writes tests/data/kv_handoff_parent_pr30.{pkkv,json}: a KV handoff blob
and the greedy continuation of one tiny-llama prompt, produced by the tree
whose pools were stored [L, N, page_size, Hk, D] (commit 4c8c7cb, before
ISSUE 34 folded the heads). tests/test_paged_layout.py restores the blob on
the tree as it stands: the wire format is a contract between builds, so this
script is run ONCE, from that commit, and its outputs are checked in.

    JAX_PLATFORMS=cpu PYTHONPATH=. python tests/data/make_parent_kv_blob.py
"""

import json
import os

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.kv_cache import serialize_kv_state

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = dict(
    model="tiny-llama", dtype="float32", max_decode_slots=2, page_size=8,
    num_pages=32, max_seq_len=64, prefill_buckets=(16, 32),
    decode_block_steps=2, adaptive_block=False, max_new_tokens_cap=12,
    default_max_new_tokens=12, supervise=False,
)
SEED = 7
PROMPT = "parent layout handoff"
NEW_TOKENS = 10


def drain(engine, **kw):
    request = GenRequest(prompt=PROMPT, max_new_tokens=NEW_TOKENS, seed=1, **kw)
    engine.submit(request)
    tokens, state = [], None
    while True:
        kind, value = request.out.get(timeout=120)
        if kind == "token":
            tokens.append(int(value))
        elif kind == "handoff":
            state = value
        elif kind == "done":
            return tokens, state
        else:
            raise RuntimeError(value)


def main():
    engine = InferenceEngine(EngineConfig(**CONFIG), seed=SEED)
    try:
        tokens, _ = drain(engine)
        _, state = drain(engine, prefill_only=True)
    finally:
        engine.shutdown()
    with open(os.path.join(HERE, "kv_handoff_parent_pr30.pkkv"), "wb") as f:
        f.write(serialize_kv_state(state))
    with open(os.path.join(HERE, "kv_handoff_parent_pr30.json"), "w") as f:
        json.dump({
            "commit": "4c8c7cb163085550296f4c3a200086745de57368",
            "config": CONFIG, "seed": SEED, "prompt": PROMPT,
            "tokens": tokens, "first_token": int(state.first_token),
            "k_shape": list(state.k.shape),
        }, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    main()
