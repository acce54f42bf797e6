"""The one general traffic generator: reads a mix's data file, makes the
work of a run from ``--seed``.

A mix (``benchmark/traffic/<name>.json``) has

    loop        "closed" (a fixed number of clients, each sends its next
                request when the last one finished) or "open" (arrivals on a
                schedule, whatever the server does)
    cycle       [[prompt_len, output_len], ...] in one fixed order. Requests
                take consecutive entries of this ONE cycle in the order they
                are sent, whichever client sends them, so any stretch of a
                run holds the same multiset of lengths.
    prompt_pool, tokens_seed
                the stream of requests repeats after ``prompt_pool`` cycles;
                prompt j of a period is made of random token ids from
                (tokens_seed, j). ``--seed`` moves only where in that
                stream a run starts (and an open loop's jitter).
    seed_step   optional: "entry" (the default: a run starts at entry
                ``seed % period`` of the stream) or "cycle" (it starts at
                the head of cycle ``seed % prompt_pool``). A window is a
                cut in time and holds a whole number of cycles plus a
                remainder; with "entry" the remainder's lengths, and so the
                work, differ by seed, with "cycle" every seed sends the same
                lengths in the same order and only the token ids move. Take
                "cycle" wherever a window holds only a few cycles.
    closed:     clients ("slots" = the configuration's batch slots, or a
                number), warmup_s (least seconds of load before the window)
    open:       rate_rps, jitter (share of the gap, uniform, each way),
                pre_s / post_s (seconds of the same arrivals before and
                after the window, so the window sees a steady state)

A later PR adds a mix by adding a file; nothing here names a mix.
"""

from __future__ import annotations

import json
from typing import List, Tuple

import numpy as np

COMMON_KEYS = {"loop", "cycle", "prompt_pool", "tokens_seed"}
CLOSED_KEYS = COMMON_KEYS | {"clients", "warmup_s"}
OPEN_KEYS = COMMON_KEYS | {"rate_rps", "jitter", "pre_s", "post_s"}


def load_traffic(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    loop = t.get("loop")
    if loop not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    need = CLOSED_KEYS if loop == "closed" else OPEN_KEYS
    missing = need - set(t)
    if missing:
        raise ValueError(f"{path}: missing {sorted(missing)}")
    cyc = t["cycle"]
    if not cyc or any(len(p) != 2 or min(p) < 1 for p in cyc):
        raise ValueError(f"{path}: cycle must be [[prompt, output], ...]")
    return t


class Cycle:
    """The mix's fixed stream of requests; the seed picks where a run
    starts in it.

    Entry j of the stream has the lengths ``cycle[j % len(cycle)]`` and the
    prompt number ``j % (len(cycle) * prompt_pool)``, whose token ids come
    from the mix's own ``tokens_seed``: the stream is periodic, the same
    for every ``--seed``, and ``--seed`` only rotates it (and, in an open
    loop, draws the arrivals' jitter). Token ids are part of the work
    where the program speculates (they decide what a draft gets accepted),
    so they may not move with the seed any more than the lengths do."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.pairs = [(int(p), int(o)) for p, o in traffic["cycle"]]
        self.period = len(self.pairs) * int(traffic["prompt_pool"])
        self.tokens_seed = int(traffic["tokens_seed"])
        self.vocab = int(vocab)
        step = traffic.get("seed_step", "entry")
        if step == "entry":
            self.j = int(seed) % self.period
        elif step == "cycle":
            self.j = (int(seed) % int(traffic["prompt_pool"])) * len(self.pairs)
        else:
            raise ValueError("seed_step must be 'entry' or 'cycle'")

    def next(self) -> Tuple[List[int], int]:
        """(prompt token ids, output length) of the next request."""
        n_in, n_out = self.pairs[self.j % len(self.pairs)]
        rng = np.random.default_rng([self.tokens_seed, self.j])
        self.j = (self.j + 1) % self.period
        return rng.integers(1, self.vocab, size=n_in).tolist(), n_out


def open_schedule(traffic: dict, seed: int, seconds: float):
    """Evenly paced arrivals with uniform jitter, as offsets from the
    window's start: ``round(rate * pre_s)`` before it, exactly
    ``round(rate * seconds)`` due inside it, ``round(rate * post_s)`` after.
    Returns (offsets, n_pre, n_window); the same counts for every seed."""
    rate = float(traffic["rate_rps"])
    n_win = max(1, round(rate * seconds))
    gap = seconds / n_win
    n_pre = round(float(traffic["pre_s"]) / gap)
    n_post = round(float(traffic["post_s"]) / gap)
    jitter = float(traffic["jitter"])
    if not 0 <= jitter < 0.5:
        raise ValueError("jitter must be in [0, 0.5): arrivals keep order "
                         "and stay on their side of the window's edges")
    rng = np.random.default_rng([int(seed), 1])
    u = rng.uniform(-1.0, 1.0, size=n_pre + n_win + n_post)
    idx = np.arange(-n_pre, n_win + n_post)
    offsets = (idx + 0.5 + jitter * u) * gap
    return offsets.tolist(), n_pre, n_win
