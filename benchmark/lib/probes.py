"""`correct`: probe prompts outside the window, greedy, asking for the
sampled token's logprob (`logprobs: 0`: the plain step programs, the ones
the window runs); the served path's logprob of its top-1 token against the
plain reference's logprob of ITS top-1 token after the same prefix.

The probes are token strings from the configuration's `weights_seed`, each
PROBE_STEPS - 1 tokens longer than its prompt; their lengths are the mix's
`probe_lens`, or PROBE_LENS where it names none.  Short ones run the
short-table programs; a long one runs what long prompts run: 512-token
chunks, wide page tables, the Pallas prefill kernel.  Text i depends on i
and its own length alone, so a mix that appends a length to the default
keeps the default's texts and adds one.

Two checks, both must hold:
  forced   the probe's first L + k tokens as the prompt, one token generated
           (k = 0..PROBE_STEPS-1): a prefill whose context is known; k = 0
           cold, k > 0 a short chunk against the pages the earlier requests
           left in the prefix cache.  Every step compares.
  greedy   the probe's first L tokens, `depth` tokens generated by the
           served path, `depth` = the most tokens a request of the cell's mix
           asks for, at most PROBE_STEPS: step 0 is the prefill's, later
           steps decode from the token the host fed back.  Skipped where the
           mix asks for one token (it would repeat forced k = 0), and made
           only with the probes of at most GREEDY_MAX_LEN tokens (every
           reference step is a whole forward pass).

The served token's id is not visible (the frontend returns token strings,
and most ids of a random-weight model detokenize to nothing under the test
tokenizer), so top-1 is compared with top-1.  Where the reference's top two
lie within the reference file's TIE_MARGIN, bf16 may pick the other: that
step is allowed the gap on top of the tolerance, and a greedy comparison
ends there (the contexts part)."""

import random

PROBE_LENS = (48, 48, 48, 48, 1200)
PROBE_STEPS = 8
GREEDY_MAX_LEN = 256


def lens_of(mix):
    """The mix's probe lengths: its `probe_lens`, else PROBE_LENS."""
    lens = tuple(int(n) for n in mix.get("probe_lens", PROBE_LENS))
    if not lens or min(lens) < 1:
        raise ValueError(f"probe_lens {lens}: one positive length at least")
    return lens


def longest_request(lens):
    """Prompt plus answer of the longest probe request: its last forced step
    sends L + PROBE_STEPS - 1 tokens and asks for one."""
    return max(lens) + PROBE_STEPS


def probe_texts(weights_seed, vocab, lens=PROBE_LENS):
    """Seeded by the configuration's weights_seed (not by --seed), so that
    the reference's answer is computed once per checkout.  Text i has
    lens[i] + PROBE_STEPS - 1 tokens."""
    ids = range(vocab[0], vocab[1])
    return [random.Random(f"probe:{weights_seed}:{i}").choices(
        ids, k=n + PROBE_STEPS - 1) for i, n in enumerate(lens)]


def greedy_depth(max_output_len):
    return min(PROBE_STEPS, int(max_output_len))


def allowed(r, tol, tie_margin):
    return tol + (r["gap"] if r["gap"] < tie_margin else 0.0)


def compare_forced(served, ref, tol, tie_margin):
    """served[i][k]: the served logprob of its top-1 token after text i's
    first L + k tokens.  ref[i][k]: {"logprob", "gap"} of the reference's
    top-1 there.  Returns (ok, detail); `steps_over_by_probe` counts, per
    probe, the steps past what they are allowed, so that a fault that only a
    long context shows is seen to fail the long probe alone;
    `max_past_allowed` is the largest difference less what its step is
    allowed (the tolerance, plus the gap on a near-tie): the one number the
    check turns on, at or under 0 where it holds."""
    worst, compared, problems, over, excess = 0.0, 0, [], [], None
    for i, (s_steps, r_steps) in enumerate(zip(served, ref)):
        over.append(0)
        if len(s_steps) != len(r_steps):
            problems.append(f"probe {i}: {len(s_steps)} served steps, "
                            f"{len(r_steps)} reference steps")
            continue
        for k, (s, r) in enumerate(zip(s_steps, r_steps)):
            diff = abs(s - r["logprob"])
            compared += 1
            past = diff - allowed(r, tol, tie_margin)
            excess = past if excess is None else max(excess, past)
            if past > 0:
                over[i] += 1
                problems.append(
                    f"probe {i} prefix +{k}: served {s:.4f} vs reference "
                    f"{r['logprob']:.4f} (top-2 gap {r['gap']:.4f})")
            elif r["gap"] >= tie_margin:
                worst = max(worst, diff)
    ok = not problems and len(served) == len(ref) and compared > 0
    return ok, {"max_abs_logprob_diff": worst, "steps_compared": compared,
                "max_past_allowed": excess, "steps_over_by_probe": over,
                "problems": problems[:4]}


def compare_greedy(served, ref, tol, tie_margin):
    """served: per probe, the logprobs of the tokens it generated.  ref: per
    probe, {"logprob", "gap"} of the reference's own greedy steps."""
    worst, compared, problems = 0.0, 0, []
    for i, (s_steps, r_steps) in enumerate(zip(served, ref)):
        if len(s_steps) != len(r_steps):
            problems.append(f"probe {i}: {len(s_steps)} served steps, "
                            f"{len(r_steps)} reference steps")
            continue
        for k, (s, r) in enumerate(zip(s_steps, r_steps)):
            diff = abs(s - r["logprob"])
            compared += 1
            if diff > allowed(r, tol, tie_margin):
                problems.append(
                    f"probe {i} step {k}: served {s:.4f} vs reference "
                    f"{r['logprob']:.4f} (top-2 gap {r['gap']:.4f})")
                break
            if r["gap"] < tie_margin:
                break
            worst = max(worst, diff)
    ok = not problems and len(served) == len(ref) and compared >= len(ref)
    return ok, {"max_abs_logprob_diff": worst, "steps_compared": compared,
                "problems": problems[:4]}
