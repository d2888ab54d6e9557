#!/usr/bin/env python3
"""What a router's near-ties cost a cell's `correct`, on the host alone:

    python3 benchmark/tests/routing_answers.py <cell> [<texts> [<ckpt dir>]]

For a family whose reference `forward` takes `bf16_stream`, `picks` and
`routing` (reference/lfm2_moe.py).  `<texts>` 48-token probe texts (8) of
the configuration's draw, every position past the seventh a step (48 a
text, as many as the cell's check compares): the top-1 logprob of the plain
float32 reference against the same file with the stream rounded as the
served path rounds it (`bf16_stream`), and with the whole model at 3 bits of
mantissa (`lower_precision`), each twice: choosing its own experts, and HELD
to the plain reference's choices (`routing`).  Per reading: quantiles of the
difference less the tie allowance, the share of steps past some limits, and
the largest of every 48 steps, which is what one check would read.  If the
held readings lie an order under the free ones, what moves `correct` is the
experts the two sides choose and not their arithmetic.  The checkpoint is
the one a run left under benchmark/.cache/ckpt, or `<ckpt dir>`; nothing is
served and no chip is touched.  Not part of a benchmark run."""

import glob
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import checkpoint, probes  # noqa: E402

READINGS = (("bf16_stream", {"bf16_stream": True}),
            ("lower_precision", {"lower_precision": True}))
LIMITS = (0.03, 0.06, 0.1, 0.17, 0.23, 0.3)
QUANTILES = (0.5, 0.9, 0.99, 1.0)


def main(cell_name, texts=8, ckpt_dir=None):
    import ml_dtypes  # noqa: F401 — registers bfloat16 with numpy
    import numpy as np
    from safetensors import safe_open

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == cell_name]
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    ref = checkpoint.load_module("reference", config["reference"])
    if ckpt_dir is None:
        (ckpt_dir,) = glob.glob(os.path.join(BENCH, ".cache", "ckpt",
                                             cell["config"] + "-*"))
    reader = safe_open(os.path.join(ckpt_dir, "model.safetensors"),
                       framework="np")

    def read(name):
        return reader.get_tensor(name).astype(np.float32)

    steps = 48
    batch = [np.asarray(probes.probe_texts(
        config["weights_seed"], tuple(config["prompt_vocab"]),
        (steps,) * texts))]

    def top(**controls):
        lp = np.sort(ref.forward(read, config["model"], batch, steps,
                                 **controls)[0], -1)
        return lp[..., -1], lp[..., -1] - lp[..., -2]

    picks = {}
    plain, gap = top(picks=picks)
    tie = np.where(gap < ref.TIE_MARGIN, gap, 0.0)
    out = {"cell": cell_name, "steps": int(plain.size),
           "tie_margin": ref.TIE_MARGIN, "tolerance": ref.LOGPROB_TOL}
    for name, controls in READINGS:
        for how, routing in (("free", None), ("held", picks)):
            past = np.abs(top(routing=routing, **controls)[0] - plain) - tie
            out[f"{name}.{how}"] = {
                "quantiles": {str(q): float(np.quantile(past, q))
                              for q in QUANTILES},
                "share_past": {str(t): float((past > t).mean())
                               for t in LIMITS},
                "max_of_each_48": [float(v) for v in past.max(-1)]}
            print(json.dumps({f"{name}.{how}": out[f"{name}.{how}"]}),
                  file=sys.stderr, flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]), *sys.argv[3:4])
