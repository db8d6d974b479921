"""The one traffic generator: a traffic file + a seed -> what each client sends.

The SHAPE of the traffic — the (prompt tokens, output tokens) rows, which
client owns which, the think-time range, the shared prefix, the phasing of
the first requests — is the traffic file's and is the same for every seed.
The seed chooses the order of a client's rows, the order of its think
times and the prompt characters. So every seed carries the same multiset of
sizes and of waits.

Whether the requests are sampled is the CONFIGURATION's to say, as a
model's generation parameters are published with the model: a
configuration file with a "sampling" group (temperature, top_k, top_p: the
request parameters of the served API) is sent sampled requests under any
traffic file. Every request then carries those parameters and a sampling
seed of its own, derived from (seed, client, request index), so that the
same `--seed` asks for the same streams. A configuration without the group
is sent greedy requests, as before the group existed.

Prompt characters are printable ASCII: with the package's ByteTokenizer a
prompt of n tokens is BOS + (n - 1) characters.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import string
from dataclasses import dataclass

ALPHABET = string.ascii_letters + string.digits + " .,;:-_()"
# Printable ASCII as ByteTokenizer ids (id = 3 + byte): what the server
# child narrows the output head to, so every token streams as a character.
FIRST_ID, LAST_ID = 3 + 0x20, 3 + 0x7E
HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Request:
    client: int
    index: int            # k-th request of this client
    prompt: str
    prompt_tokens: int    # BOS + characters
    output_tokens: int
    think_s: float        # wait before sending (after the previous reply)
    sampling: dict | None = None   # request parameters of a sampled request


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def scaled(traffic: dict, tiny: bool) -> dict:
    """The CPU rehearsal divides every length; the shape stays."""
    if not tiny:
        return traffic
    tiny = traffic["tiny"]
    div_p, div_o = tiny["divide_prompt"], tiny["divide_output"]
    floor = tiny["min_tokens"]
    small = dict(traffic)
    small["shared_prefix_tokens"] = traffic["shared_prefix_tokens"] // div_p
    small["rows_by_client"] = [
        [[max(floor, p // div_p), max(floor, o // div_o)] for p, o in rows]
        for rows in traffic["rows_by_client"]
    ]
    return small


class Plan:
    """request(client, k) is a pure function of (traffic file, seed)."""

    def __init__(self, traffic: dict, seed: int, sampling: dict | None = None):
        self.traffic = traffic
        self.seed = seed
        self.sampled = dict(sampling) if sampling else None
        self.clients = traffic["clients"]
        rows = traffic["rows_by_client"]
        if len(rows) != self.clients:
            raise ValueError("rows_by_client needs one list per client")
        lo, hi = traffic["think_ms"]
        self.rows, self.thinks = [], []
        for i, mine in enumerate(rows):
            order = list(mine)
            random.Random(f"{seed}/order/{i}").shuffle(order)
            self.rows.append(order)
            # Think times are evenly spaced quantiles of uniform(lo, hi),
            # one per row, in an order of their own: every seed gives a
            # client the same set of waits.
            thinks = [lo + (hi - lo) * (j + 0.5) / len(mine)
                      for j in range(len(mine))]
            random.Random(f"{seed}/think/{i}").shuffle(thinks)
            self.thinks.append(thinks)
        shared = traffic["shared_prefix_tokens"]
        # BOS is the prefix's first token.
        self.prefix = self._text(f"{seed}/prefix", max(0, shared - 1))
        self.shared = shared

    @staticmethod
    def _text(key: str, chars: int) -> str:
        rng = random.Random(key)
        return "".join(rng.choices(ALPHABET, k=chars))

    def sampling(self, client: int, k: int) -> dict | None:
        """The configuration's sampling parameters with this request's own
        seed (48 bits: a Struct number is a double), or None for greedy.
        `client` -1 is the harness's own sample (run.serve_sample)."""
        group = self.sampled
        if not group:
            return None
        digest = hashlib.sha256(
            f"{self.seed}/sampling/{client}/{k}".encode()).digest()
        return {**group, "seed": int.from_bytes(digest[:6], "big")}

    def request(self, client: int, k: int) -> Request:
        mine = self.rows[client]
        prompt_tokens, output_tokens = mine[k % len(mine)]
        if k == 0 and self.traffic.get("first_request_phasing"):
            # Client i of N ends its first request after (i+1)/N of its
            # length, so turnovers come spread out and not in waves.
            cut = output_tokens * (client + 1) / self.clients
            output_tokens = max(8, 8 * round(cut / 8))
        own = prompt_tokens - max(1, self.shared)
        if own < 0:
            raise ValueError("a prompt is shorter than the shared prefix")
        text = self.prefix + self._text(f"{self.seed}/p/{client}/{k}", own)
        think = self.thinks[client][k % len(mine)]
        return Request(client, k, text, len(text) + 1, output_tokens,
                       0.0 if k == 0 else think / 1000.0,
                       self.sampling(client, k))

    def shape(self) -> list:
        """What must not depend on the seed: per client, the sorted rows
        and the sorted think times."""
        return [(sorted(map(tuple, mine)), sorted(thinks))
                for mine, thinks in zip(self.rows, self.thinks)]
