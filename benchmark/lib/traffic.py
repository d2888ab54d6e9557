"""The one traffic generator.  A mix is a data file (benchmark/traffic/
<mix>.json); this module turns it and a seed into requests.

A *session* is `turns` requests sent one after the other; each prompt is the
session's shared prefix (`prefix_len` tokens, may be 0) followed by fresh
tokens.  A plain request is a session of one turn without a prefix.

What `--seed` draws: the ORDER of the sessions (another permutation of the
set in every cycle) and every token.  What it does not draw: the set of
sizes.  A mix names `set_size` sessions whose lengths are drawn once, from
the mix's own `sizes_seed`; every seed sends that same set, in another order
(the builder's contract: "give every seed the same set of sizes, in another
order").  The set is kept small enough that a window goes through all of it,
so every seed's window does the same work; and a loop that has sent the whole
set once has met every shape the window can meet.  A mix is therefore ONE
sample of its length distributions: a change that moves a bucket boundary
lands on that sample (PERF.md, Open questions).

Mix keys:
  loop          the loop that sends it: benchmark/loops/<loop>.py
  clients       sessions in flight
  set_size      sessions in the set
  sizes_seed    seeds the set's lengths
  turns         requests per session
  prefix_len    length spec of the shared prefix, or absent
  fresh_len     length spec of each turn's fresh tokens
  output_len    length spec of each turn's max_tokens
  warmup        the loop's warm-up recipe (see the loop's file)
  probe_lens    lengths of `correct`'s probe prompts (lib/probes.py); absent:
                its default, which ends at 1,200 tokens.  A mix of longer
                prompts names a probe as long as they are
A length spec is {"dist": "fixed", "value"} | {"dist": "uniform", "min",
"max"} | {"dist": "lognormal", "median", "sigma", "min", "max"}.
"""

import json
import math
import random


def load_mix(path):
    with open(path) as f:
        return json.load(f)


def draw_len(rng, spec):
    if spec is None:
        return 0
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    if dist == "uniform":
        return rng.randint(int(spec["min"]), int(spec["max"]))
    if dist == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
        return int(min(max(round(x), spec["min"]), spec["max"]))
    raise ValueError(f"unknown length distribution {dist!r}")


def max_len(spec):
    """The longest length the spec CAN draw (0 for an absent spec)."""
    if spec is None:
        return 0
    if spec["dist"] == "fixed":
        return int(spec["value"])
    if spec["dist"] in ("uniform", "lognormal"):
        return int(spec["max"])
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def longest_request(mix):
    """Prompt plus answer of the longest request the mix's distributions can
    draw, whatever its one set drew: what the worker's context has to hold."""
    return (max_len(mix.get("prefix_len")) + max_len(mix["fresh_len"])
            + max_len(mix["output_len"]))


def session_sizes(mix):
    """The set: `set_size` sessions' lengths, the same for every seed."""
    rng = random.Random(f"sizes:{mix['sizes_seed']}")
    return [{"prefix_len": draw_len(rng, mix.get("prefix_len")),
             "turns": [(draw_len(rng, mix["fresh_len"]),
                        draw_len(rng, mix["output_len"]))
                       for _ in range(int(mix.get("turns", 1)))]}
            for _ in range(int(mix["set_size"]))]


def max_output_len(mix):
    return max(out for s in session_sizes(mix) for _, out in s["turns"])


def sessions(mix, seed, vocab, cycle=0):
    """One cycle through the set: every session once, in the order `seed`
    and `cycle` draw.  `vocab` = (lo, hi): prompt token ids are drawn from
    [lo, hi).  Contents depend on (seed, cycle, position in the set)."""
    sizes = session_sizes(mix)
    order = list(range(len(sizes)))
    random.Random(f"order:{seed}:{cycle}").shuffle(order)
    ids = range(vocab[0], vocab[1])
    out = []
    for i in order:
        s = sizes[i]
        rng = random.Random(f"content:{seed}:{cycle}:{i}")
        prefix = rng.choices(ids, k=s["prefix_len"])
        out.append([{"prompt": prefix + rng.choices(ids, k=fresh),
                     "max_tokens": out_len}
                    for fresh, out_len in s["turns"]])
    return out
