"""Child process (numpy only; never touches the chip): the plain reference's
answers to the probes, written as JSON.
`python reference_child.py <config file> <checkpoint dir> <out file> <depth>
[<probe lengths, JSON> [<control>]]`.
One forward pass over every probe text gives the forced steps; a greedy
depth above 1 costs depth - 1 more passes of the short probes.  Streams one
tensor at a time from the checkpoint.  A probe of thousands of tokens is the
family's to fit into the host: its `tail_logprobs` computes attention in
blocks of queries (reference/smallthinker.py), never a whole score matrix.

`<control>` names a keyword of the family's `forward` that a control run
switches on (`lower_precision`, `ignore_window`, ...): the answers are then
the CONTROL's, for tests/control_answers.py to hold the served path's
against; `run.py` never passes one."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import checkpoint, probes  # noqa: E402


def top1(np, lp):
    """lp [vocab] -> {"token", "logprob", "gap" to the second}."""
    a, b = np.argpartition(lp, -2)[-2:]
    if lp[a] > lp[b]:
        a, b = b, a
    return {"token": int(b), "logprob": float(lp[b]),
            "gap": float(lp[b] - lp[a])}


def main(config_path, ckpt_dir, out_path, depth, lens=probes.PROBE_LENS,
         control=None):
    import ml_dtypes  # noqa: F401 — registers bfloat16 with numpy
    import numpy as np
    from safetensors import safe_open

    with open(config_path) as f:
        config = json.load(f)
    ref = checkpoint.load_module("reference", config["reference"])
    model = config["model"]
    reader = safe_open(os.path.join(ckpt_dir, "model.safetensors"),
                       framework="np")

    def read(name):
        return reader.get_tensor(name).astype(np.float32)

    def tails(rows, n_last):
        """rows: {probe index: tokens}.  One pass; rows of one length share
        a batch.  Returns {probe index: [n_last, vocab] logprobs}."""
        lens = sorted({len(r) for r in rows.values()})
        groups = [[i for i in rows if len(rows[i]) == n] for n in lens]
        batches = [np.asarray([rows[i] for i in g]) for g in groups]
        if control is None:
            lp = ref.tail_logprobs(read, model, batches, n_last)
        else:
            lp = ref.forward(read, model, batches, n_last, **{control: True})
        return {i: lp[b][j] for b, g in enumerate(groups)
                for j, i in enumerate(g)}

    texts = probes.probe_texts(config["weights_seed"],
                               tuple(config["prompt_vocab"]), lens)
    steps = probes.PROBE_STEPS
    lp = tails(dict(enumerate(texts)), steps)
    forced = [[top1(np, lp[i][k]) for k in range(steps)]
              for i in range(len(texts))]
    out = {"forced": forced, "depth": depth, "tolerance": ref.LOGPROB_TOL,
           "tie_margin": ref.TIE_MARGIN, "probe_lens": list(lens),
           "control": control}
    if depth > 1:
        short = [i for i, n in enumerate(lens)
                 if n <= probes.GREEDY_MAX_LEN]
        rows = {i: list(texts[i][:lens[i]]) for i in short}
        greedy = {i: [forced[i][0]] for i in short}
        for _ in range(depth - 1):
            for i in short:
                rows[i].append(greedy[i][-1]["token"])
            lp = tails(rows, 1)
            for i in short:
                greedy[i].append(top1(np, lp[i][0]))
        out["greedy_probes"] = short
        out["greedy"] = [greedy[i] for i in short]
    with open(out_path + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(out_path + ".tmp", out_path)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4]),
         tuple(json.loads(sys.argv[5])) if len(sys.argv) > 5
         else probes.PROBE_LENS,
         sys.argv[6] if len(sys.argv) > 6 else None)
