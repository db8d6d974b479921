"""What the tests of the layer-pattern stacks share (tests/test_hybrid.py,
tests/test_lfm2.py): one slot batch of a toy model driven through
`forward_slots` + `unembed` the way the engine's prefill and decode steps
call them, and a toy engine's streams held to a plain reference."""

import time

import jax.numpy as jnp
import numpy as np

from polykey_tpu.engine.engine import GenRequest
from polykey_tpu.engine.kv_cache import init_paged_kv, init_slot_state
from polykey_tpu.models.hybrid import PrefillRows
from polykey_tpu.models.transformer import forward_slots, unembed

SLOTS, PAGE, PAGES_PER_SEQ = 4, 8, 24
NOWHERE = SLOTS          # a store index past the last slot: dropped


class SlotBatch:
    """`SLOTS` slots of `cfg`, each with its own `PAGES_PER_SEQ` pages;
    `ref.forward` is what every logit is compared with, within `tol`."""

    def __init__(self, cfg, ref, tol):
        self.cfg, self.ref, self.tol = cfg, ref, tol

    def fresh(self, dtype=jnp.float32):
        return (init_paged_kv(self.cfg, 1 + SLOTS * PAGES_PER_SEQ, PAGE, dtype),
                init_slot_state(self.cfg, SLOTS, dtype))

    @staticmethod
    def table(slot):
        first = 1 + slot * PAGES_PER_SEQ
        return np.arange(first, first + PAGES_PER_SEQ, dtype=np.int32)

    def prefill(self, params, paged, state, slot, ids, start, width, sources,
                store_last=True):
        """`ids` from position `start` as len(sources) rows of `width` in
        ONE dispatch; returns (logits of the real positions, paged, state)."""
        n = len(sources)
        toks = np.zeros((n, width), np.int32)
        lengths = []
        for r in range(n):
            part = ids[r * width:(r + 1) * width]
            toks[r, :len(part)] = part
            lengths.append(len(part))
        positions = (start + np.arange(n)[:, None] * width
                     + np.arange(width)[None])
        store = [NOWHERE] * (n - 1) + [slot if store_last else NOWHERE]
        rows = PrefillRows(
            jnp.full((n,), slot, jnp.int32), jnp.asarray(sources, jnp.int32),
            jnp.asarray(store, jnp.int32), jnp.asarray(lengths, jnp.int32))
        hidden, paged, state = forward_slots(
            params, self.cfg, jnp.asarray(toks),
            jnp.asarray(positions, jnp.int32), paged,
            jnp.tile(self.table(slot)[None], (n, 1)), state, rows=rows)
        logits = unembed(
            params, self.cfg, hidden.reshape(n * width, -1)[:len(ids)])
        return np.asarray(logits), paged, state

    def decode(self, params, paged, state, slot, token, position, active=True):
        last = np.zeros((SLOTS,), np.int32)
        pos = np.zeros((SLOTS, 1), np.int32)
        tables = np.zeros((SLOTS, PAGES_PER_SEQ), np.int32)
        act = np.zeros((SLOTS,), bool)
        if active:
            last[slot], pos[slot, 0] = token, position
            tables[slot], act[slot] = self.table(slot), True
        hidden, paged, state = forward_slots(
            params, self.cfg, jnp.asarray(last)[:, None], jnp.asarray(pos),
            paged, jnp.asarray(tables), state, active=jnp.asarray(act))
        return (np.asarray(unembed(params, self.cfg, hidden[slot, 0])), paged,
                state)

    def decode_tail(self, params, paged, state, slot, ids, start, want):
        """Teacher-forced decode of ids[start:], compared with the reference."""
        for i in range(start, len(ids)):
            got, paged, state = self.decode(
                params, paged, state, slot, ids[i], i)
            np.testing.assert_allclose(got, want[i], atol=self.tol, rtol=0)


def served(engine, prompts, new=10):
    """The ids streamed for each prompt, all submitted at once; `new` is
    one length for all or one a prompt."""
    lengths = new if isinstance(new, (list, tuple)) else [new] * len(prompts)
    requests = [GenRequest(prompt=p, max_new_tokens=n)
                for p, n in zip(prompts, lengths)]
    for request in requests:
        engine.submit(request)
    out = []
    for request in requests:
        ids, deadline = [], time.monotonic() + 120
        while True:
            kind, value = request.out.get(timeout=deadline - time.monotonic())
            if kind == "token":
                ids.append(value)
            elif kind == "done":
                break
            else:
                raise AssertionError(value)
        out.append(ids)
    return out


def worst_margin(ref, engine, prompt, ids):
    """Teacher-force the reference with the served tokens: how far below
    the reference's best logit each served token lies, at worst."""
    prompt_ids = engine.tokenizer.encode(prompt)
    logits = ref.forward(engine.params, engine.model_cfg,
                         np.asarray(prompt_ids + ids[:-1], np.int32))
    rows = logits[len(prompt_ids) - 1:]
    return max(float(np.max(row) - row[t]) for row, t in zip(rows, ids))


def text(n, salt):
    rng = np.random.default_rng(salt)
    return "".join(chr(c) for c in rng.integers(0x20, 0x7F, n - 1))
