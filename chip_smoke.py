#!/usr/bin/env python3
"""Smoke of the serving path on the chip: the quickest proof that the system
still starts on a TPU v5e.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip phases only

Writes a seeded Llama-3.2-1B-shaped bf16 checkpoint (published widths, all
16 layers, random weights) under `.chip_smoke/`, then serves it the way a
user would: `python -m dynamo_tpu.runtime`, `python -m dynamo_tpu.worker
--model <dir>` and `python -m dynamo_tpu.frontend` as OS processes, real
HTTP requests against the frontend.

One process per chip: this parent never imports jax, and no two processes
that need the chip are alive together — the device probe exits before the
first worker starts, and each worker exits before the next one starts.
The device identity comes from the worker (its `DEVICE` line beside
`READY`), everything else from its status server.

One chip (the default):
  phase A   /v1/models, unary and SSE chat + completions at the worker's
            default flags, greedy: status, usage, finish reason, SSE == unary.
  phase B   prompts long enough to reach BOTH Pallas kernels (prefill in
            512-token chunks, decode with >= 4096 tokens of table), then the same
            requests against a second worker (`--attention-impl xla`)
            started after the first has exited; top logprobs agree within
            LOGPROB_TOL.  The KV pool takes 4 GiB of the chip's 16 GB (see
            KV_PAGES for why not more).
            The second worker also repeats phase A: the programs it shares
            with the first come from the persistent compile cache.
Four chips (`--chips 4`): a one-chip worker's greedy answers, then a
`--tp 4` worker's answers to the same requests (equal), then `--dp-ranks 4`
behind `--router-mode kv` with every replica's memory on its own device.

One JSON line per phase, then as the LAST line exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Any failed phase, a backend other than tpu, or an unknown device kind
exits non-zero and prints no such line.  `--rehearse-cpu` walks the same
control flow at a tiny size on the CPU backend to find wrong paths and
arguments; it never prints the result line and always exits non-zero.
"""

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")  # git-ignored, reproducible from --seed

# device kinds this repo has run on; anything else is an error, not a default
KNOWN_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")

# |delta logprob| allowed between the Pallas and the XLA attention paths (and
# between tp=4 and one chip): bf16 activations through 16 layers, two
# different accumulation orders.  bf16 keeps 8 bits of mantissa, so a logit
# of magnitude ~4 is only resolved to ~0.03; 0.1 is about three such steps.
LOGPROB_TOL = 0.1

# Llama-3.2-1B as published (meta-llama/Llama-3.2-1B config.json)
LLAMA_3_2_1B = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "vocab_size": 128256,
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 16,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 64,
    "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-5,
    "rope_theta": 500000.0,
    "rope_scaling": {
        "factor": 32.0, "high_freq_factor": 4.0, "low_freq_factor": 1.0,
        "original_max_position_embeddings": 8192, "rope_type": "llama3",
    },
    "tie_word_embeddings": True,
    "attention_bias": False,
    "hidden_act": "silu",
    "torch_dtype": "bfloat16",
    "bos_token_id": None,
}
# the --rehearse-cpu stand-in: same code paths, nothing else in common
TINY = dict(LLAMA_3_2_1B, vocab_size=1024, hidden_size=64,
            intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=4, head_dim=16)

# KV pool of the one-chip run: 8192 pages x 16 tokens x 16 layers x 2 (k, v)
# x 8 heads x 64 x 2 B = 4 GiB, a quarter of the chip's 16 GB.  Not more,
# because the v5e compiler refuses it: the pool's on-device layout is not
# the layout the step programs compute in, so every compiled step holds a
# second, re-laid-out copy of the whole pool as a temporary (arguments
# 2.3 GiB of weights + pool, temporaries pool + ~1.5 GiB).  At 8 GiB that is
# 19.8 GB of 15.75 GB (PERF.md, Findings, PR 22).
KV_PAGES = 8192


def say(**fields):
    print(json.dumps(fields), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# -- checkpoint ---------------------------------------------------------------- #

def make_checkpoint(path, hf_cfg, seed):
    """config.json + model.safetensors + the repo's test tokenizer.  Weights
    are random bf16 bit patterns drawn from the seed: random sign and
    mantissa, exponent in 2^-9..2^-6 (zero mean, std about 0.014); norm
    weights are ones.  Pure numpy — the parent stays off jax."""
    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    from dynamo_tpu.testing import tiny_tokenizer

    done = os.path.join(path, ".complete")
    stamp = json.dumps({"seed": seed, "config": hf_cfg}, sort_keys=True)
    if os.path.exists(done) and open(done).read() == stamp:
        return False
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    rng = np.random.default_rng(seed)

    def weight(*shape):
        r = rng.integers(0, 1 << 16, size=shape, dtype=np.uint16)
        bits = (r & 0x807F) | ((118 + ((r >> 7) & 3)) << 7).astype(np.uint16)
        return bits.view(ml_dtypes.bfloat16)

    def ones(n):
        return np.ones((n,), ml_dtypes.bfloat16)

    c = hf_cfg
    H, I = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    t = {"model.embed_tokens.weight": weight(c["vocab_size"], H),
         "model.norm.weight": ones(H)}
    for i in range(c["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "self_attn.q_proj.weight"] = weight(q, H)
        t[p + "self_attn.k_proj.weight"] = weight(kv, H)
        t[p + "self_attn.v_proj.weight"] = weight(kv, H)
        t[p + "self_attn.o_proj.weight"] = weight(H, q)
        t[p + "mlp.gate_proj.weight"] = weight(I, H)
        t[p + "mlp.up_proj.weight"] = weight(I, H)
        t[p + "mlp.down_proj.weight"] = weight(H, I)
        t[p + "input_layernorm.weight"] = ones(H)
        t[p + "post_attention_layernorm.weight"] = ones(H)
    save_file(t, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_cfg, f)
    with open(os.path.join(path, "tokenizer.json"), "w") as f:
        f.write(tiny_tokenizer().to_json_str())
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"eos_token": "<|endoftext|>"}, f)
    with open(done, "w") as f:
        f.write(stamp)
    return True


# -- HTTP ---------------------------------------------------------------------- #

def http(url, body=None, timeout=900):
    """(status, text).  The timeout is generous on purpose: a first request
    may wait on tens of seconds of compilation."""
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def sse_events(raw):
    return [json.loads(line[6:]) for line in raw.splitlines()
            if line.startswith("data: ") and line != "data: [DONE]"]


class Stack:
    """Control plane + frontend (both off the chip) and, one at a time, a
    worker.  Every process is a child of this one and is stopped in
    `close()`."""

    def __init__(self, log_dir, env, router_mode="round_robin"):
        sys.path.insert(0, os.path.join(ROOT, "scripts"))
        from _verify_harness import ProcSet, free_port, wait_ready

        self._wait_ready = wait_ready
        self._free_port = free_port
        self.ps = ProcSet(log_dir, env)
        self.worker = None
        port = free_port()
        self.control = f"127.0.0.1:{port}"
        # control plane and frontend run no model, but their packages
        # import jax: keep them off the chip, so that the worker is the one
        # process that holds it
        off_chip = {"JAX_PLATFORMS": "cpu"}
        cp, log = self.ps.spawn(
            [sys.executable, "-m", "dynamo_tpu.runtime", "--host",
             "127.0.0.1", "--port", str(port)], "control",
            env_extra=off_chip)
        wait_ready(cp, log)
        self.http_port = free_port()
        fe, log = self.ps.spawn(
            [sys.executable, "-m", "dynamo_tpu.frontend", "--control",
             self.control, "--host", "127.0.0.1", "--port",
             str(self.http_port), "--router-mode", router_mode], "frontend",
            env_extra=off_chip)
        wait_ready(fe, log)
        self.base = f"http://127.0.0.1:{self.http_port}"

    def start_worker(self, name, model_dir, flags, timeout=600):
        """Start a worker (the previous one must have exited), wait for
        READY and for the frontend to list the model.  Returns the device
        identity the worker printed."""
        assert self.worker is None, "one chip-holding process at a time"
        self.status_port = self._free_port()
        t0 = time.time()
        proc, log = self.ps.spawn(
            [sys.executable, "-m", "dynamo_tpu.worker", "--control",
             self.control, "--model", model_dir, "--model-name", "smoke",
             "--status-port", str(self.status_port), *flags], name)
        self._wait_ready(proc, log, needle="READY worker", timeout=timeout)
        self.worker = (proc, log)
        device = None
        with open(log) as f:
            for line in f:
                if line.startswith("DEVICE "):
                    device = json.loads(line[len("DEVICE "):])
        check(device is not None, f"{name} printed no DEVICE line")
        deadline = time.time() + 60
        while time.time() < deadline:
            st, body = http(self.base + "/v1/models")
            if st == 200 and any(m["id"] == "smoke"
                                 for m in json.loads(body)["data"]):
                return device, time.time() - t0
            time.sleep(0.5)
        raise SmokeFailure(f"frontend never listed the model of {name}")

    def report(self):
        st, body = http(f"http://127.0.0.1:{self.status_port}/metrics.json")
        check(st == 200, f"worker status server answered {st}")
        return json.loads(body)["runtime"]

    def stop_worker(self, timeout=60):
        """SIGTERM, then wait until the process is GONE (the chip is free
        only then) and the frontend has dropped the model."""
        proc, _ = self.worker
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(30)
        self.worker = None
        deadline = time.time() + 30
        while time.time() < deadline:
            st, body = http(self.base + "/v1/models")
            if st == 200 and not json.loads(body)["data"]:
                return
            time.sleep(0.5)
        raise SmokeFailure("frontend still lists the model of a dead worker")

    def close(self):
        self.ps.stop()


# -- requests ------------------------------------------------------------------ #

def chat(base, text, max_tokens, stream=False, top_logprobs=0):
    body = {"model": "smoke", "messages": [{"role": "user", "content": text}],
            "max_tokens": max_tokens, "temperature": 0,
            "nvext": {"ignore_eos": True}}
    if top_logprobs:
        body.update(logprobs=True, top_logprobs=top_logprobs)
    if stream:
        body["stream"] = True
    st, raw = http(base + "/v1/chat/completions", body)
    check(st == 200, f"chat answered {st}: {raw[:300]}")
    if not stream:
        r = json.loads(raw)
        ch = r["choices"][0]
        return {"text": ch["message"]["content"] or "",
                "finish": ch["finish_reason"], "usage": r["usage"],
                "logprobs": (ch.get("logprobs") or {}).get("content")}
    text, finish = [], None
    for ev in sse_events(raw):
        for ch in ev.get("choices", []):
            text.append(ch["delta"].get("content") or "")
            finish = ch.get("finish_reason") or finish
    return {"text": "".join(text), "finish": finish}


def phase_a(base):
    """Short requests at the worker's default flags."""
    n = 12
    prompts = ["hello world, how are you today?",
               "paged attention on tpu with jax and pallas"]
    unary = []
    for p in prompts:
        u = chat(base, p, n)
        s = chat(base, p, n, stream=True)
        check(u["finish"] == "length" and s["finish"] == "length",
              f"finish reasons {u['finish']!r} / {s['finish']!r}")
        check(u["usage"]["completion_tokens"] == n,
              f"usage {u['usage']} for max_tokens {n}")
        check(u["usage"]["prompt_tokens"] >= len(p), f"usage {u['usage']}")
        check(u["usage"]["total_tokens"] == u["usage"]["prompt_tokens"] + n,
              f"usage {u['usage']}")
        check(s["text"] == u["text"],
              f"SSE {s['text']!r} != unary {u['text']!r}")
        unary.append(u["text"])
    st, raw = http(base + "/v1/completions",
                   {"model": "smoke", "prompt": "the quick brown fox",
                    "max_tokens": n, "temperature": 0,
                    "nvext": {"ignore_eos": True}})
    check(st == 200, f"completions answered {st}: {raw[:300]}")
    r = json.loads(raw)
    check(r["usage"]["completion_tokens"] == n
          and r["choices"][0]["finish_reason"] == "length",
          f"completions {r['usage']} {r['choices'][0]['finish_reason']}")
    st, _ = http(base + "/v1/chat/completions", {"model": "nope",
                                                 "messages": []})
    check(st in (400, 404), f"unknown model answered {st}")
    return {"requests": 2 * len(prompts) + 2, "texts": unary}


def long_prompts(seed):
    """Two chat prompts of 2060 bytes (one token per byte with the test
    tokenizer, plus the template): five prefill chunks of <= 512 (the
    512-token ones run the Pallas prefill kernel under any table), and a
    decode whose table is 4096 tokens wide.  The second shares its first 1540 bytes
    with the first — a prefix-cache hit whose remainder prefills against
    >= 1024 cached tokens."""
    import random

    rng = random.Random(seed)
    alphabet = "abcdefghijklmnopqrstuvwxyz0123456789 ,.?!"
    body = "".join(rng.choice(alphabet) for _ in range(2060))
    tail = "".join(rng.choice(alphabet) for _ in range(2060 - 1540))
    return [body, body[:1540] + tail]


def phase_b_requests(base, seed):
    out = []
    for p in long_prompts(seed):
        r = chat(base, p, 8, top_logprobs=5)
        check(r["finish"] == "length" and r["usage"]["completion_tokens"] == 8,
              f"long request: {r['finish']} {r['usage']}")
        check(2048 < r["usage"]["prompt_tokens"] < 4000,
              f"long prompt is {r['usage']['prompt_tokens']} tokens")
        check(r["logprobs"] and len(r["logprobs"]) == 8,
              "long request returned no logprobs")
        out.append({"prompt_tokens": r["usage"]["prompt_tokens"],
                    "text": r["text"],
                    "steps": [[(t["token"], t["logprob"])
                               for t in step["top_logprobs"]]
                              for step in r["logprobs"]]})
    return out


def compare_logprobs(a, b, what):
    """Step by step while the greedy prefixes agree: the top-5 logprobs of
    both runs within LOGPROB_TOL.  The first token (the prefill's) must
    always compare; a later step where the two argmaxes differ ends the
    comparison of that request (the contexts differ from there on) and is
    allowed only as a tie within the tolerance."""
    worst, compared = 0.0, 0
    for ra, rb in zip(a, b):
        check(ra["prompt_tokens"] == rb["prompt_tokens"], f"{what}: prompts")
        for i, (sa, sb) in enumerate(zip(ra["steps"], rb["steps"])):
            for (_, la), (_, lb) in zip(sa, sb):
                check(math.isfinite(la) and math.isfinite(lb),
                      f"{what}: non-finite logprob at step {i}")
            diff = max(abs(la - lb) for (_, la), (_, lb) in zip(sa, sb))
            worst = max(worst, diff)
            compared += 1
            check(diff <= LOGPROB_TOL,
                  f"{what}: step {i} top logprobs differ by {diff:.4f} "
                  f"> {LOGPROB_TOL}: {sa} vs {sb}")
            if sa[0][0] != sb[0][0]:
                break  # a tie within tolerance broke the other way
    return {"max_abs_logprob_diff": round(worst, 5), "steps_compared": compared}


def paths(report, site, choice=None):
    return [p for p in report["xla"]["path_choices"]
            if p["site"] == site and (choice is None or p["choice"] == choice)]


def compile_line(report):
    x = report["xla"]
    return {"compile_seconds": x["backend_compile_seconds"],
            "compiled": x["cache_misses"], "from_cache": x["cache_hits"],
            "traced_step_variants": x["compiles_total"]}


def path_line(report):
    return {
        "attention": sorted({(p["site"], p["choice"], p["dims"])
                             for s in ("prefill_attention", "decode_attention")
                             for p in paths(report, s)}),
        "decode_path": sorted({(p["choice"], p["dims"], p["reason"])
                               for p in paths(report, "decode_step")}),
    }


def peak_bytes(report):
    return [m["peak_bytes_in_use"] for m in report["memory"]]


# -- the runs ------------------------------------------------------------------ #

def check_device(device, chips, rehearse):
    if rehearse:
        return
    check(device["platform"] == "tpu",
          f"backend is {device['platform']!r}, not tpu")
    check(device["kind"] in KNOWN_DEVICE_KINDS,
          f"unknown device kind {device['kind']!r}")
    check(device["count"] == chips,
          f"{device['count']} devices visible, this run needs {chips}")


def probe_device(env):
    """What JAX finds, asked of a child that exits before any worker
    starts (about 15 s on the chip; it spares a sandbox without one the
    checkpoint and a model load before the refusal)."""
    code = ("import json; from dynamo_tpu import chip; "
            "print(json.dumps(chip.device_identity()))")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    check(p.returncode == 0, f"device probe failed: {p.stderr[-500:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def run_one_chip(stack, ckpt, args, worker_flags):
    device, secs = stack.start_worker("worker-adaptive", ckpt, worker_flags)
    check_device(device, 1, args.rehearse_cpu)
    say(phase="worker", name="worker-adaptive", seconds=round(secs, 1),
        device=device, flags=worker_flags)

    t0 = time.time()
    a = phase_a(stack.base)
    rep = stack.report()
    say(phase="A", worker="adaptive", seconds=round(time.time() - t0, 1),
        **compile_line(rep), requests=a["requests"], native=rep["native"],
        compile_cache_dir=rep["compile_cache_dir"])

    t0 = time.time()
    b = phase_b_requests(stack.base, args.seed)
    rep = stack.report()
    say(phase="B-adaptive", seconds=round(time.time() - t0, 1),
        **compile_line(rep), **path_line(rep), peak_bytes_in_use=peak_bytes(rep),
        prompt_tokens=[r["prompt_tokens"] for r in b])
    if not args.rehearse_cpu:
        # a kernel that was meant to be reached and was not is a failure
        check(paths(rep, "prefill_attention", "pallas"),
              "the Pallas prefill kernel was never reached")
        check(paths(rep, "decode_attention", "pallas"),
              "the Pallas decode kernel was never reached")
        check(rep["native"] == {"radix_index": "native",
                                "block_hash": "native"},
              f"Python twins in use: {rep['native']}")
    check(paths(rep, "decode_step", "block"), "no decode step took the block path")
    stack.stop_worker()

    device2, secs = stack.start_worker(
        "worker-xla", ckpt, [*worker_flags, "--attention-impl", "xla"])
    check(device2 == device, f"second worker sees {device2}, first {device}")
    say(phase="worker", name="worker-xla", seconds=round(secs, 1))
    t0 = time.time()
    a2 = phase_a(stack.base)
    rep2 = stack.report()
    check(a2["texts"] == a["texts"], "phase A answers changed between workers")
    # the short-context programs are the same for both workers, so the
    # second one loads them from the cache the first one filled
    say(phase="A-warm", worker="xla", seconds=round(time.time() - t0, 1),
        **compile_line(rep2))
    t0 = time.time()
    b2 = phase_b_requests(stack.base, args.seed)
    rep2 = stack.report()
    check(not paths(rep2, "prefill_attention", "pallas")
          and not paths(rep2, "decode_attention", "pallas"),
          "the reference worker ran a Pallas kernel")
    agree = compare_logprobs(b, b2, "pallas vs xla")
    say(phase="B-xla", seconds=round(time.time() - t0, 1),
        **compile_line(rep2), **agree, tolerance=LOGPROB_TOL,
        peak_bytes_in_use=peak_bytes(rep2),
        same_text=[x["text"] == y["text"] for x, y in zip(b, b2)])
    stack.stop_worker()
    return device


def run_four_chips(stack, ckpt, args, worker_flags):
    """(a) one chip, then tp=4, the same greedy requests; (b) four replicas
    of one process, each on its own chip, behind the KV router."""
    device, secs = stack.start_worker("worker-1chip", ckpt, worker_flags)
    check_device(device, 4, args.rehearse_cpu)
    t0 = time.time()
    a = phase_a(stack.base)
    b = phase_b_requests(stack.base, args.seed)
    rep = stack.report()
    used = [m["bytes_in_use"] or 0 for m in rep["memory"]]
    say(phase="tp-reference", seconds=round(time.time() - t0, 1),
        load_seconds=round(secs, 1), **compile_line(rep),
        bytes_in_use=used)
    stack.stop_worker()

    _, secs = stack.start_worker(
        "worker-tp4", ckpt, [*worker_flags, "--tp", "4"], timeout=900)
    t0 = time.time()
    a4 = phase_a(stack.base)
    b4 = phase_b_requests(stack.base, args.seed)
    rep = stack.report()
    check(a4["texts"] == a["texts"],
          f"tp=4 short answers differ: {a4['texts']} vs {a['texts']}")
    check([r["text"] for r in b4] == [r["text"] for r in b],
          "tp=4 long answers differ from one chip")
    agree = compare_logprobs(b, b4, "tp=4 vs one chip")
    for x, y in zip(b, b4):
        check([s[0][0] for s in x["steps"]] == [s[0][0] for s in y["steps"]],
              "tp=4 greedy tokens differ from one chip")
    used = [m["bytes_in_use"] or 0 for m in rep["memory"]]
    say(phase="tp4", seconds=round(time.time() - t0, 1),
        load_seconds=round(secs, 1), **compile_line(rep), **agree,
        tolerance=LOGPROB_TOL, greedy_equal=True, bytes_in_use=used)
    if not args.rehearse_cpu:
        check(all(u > 0 for u in used) and len(used) == 4,
              f"tp=4 left a chip empty: {used}")
    stack.stop_worker()

    _, secs = stack.start_worker(
        "worker-dp4", ckpt, [*worker_flags, "--dp-ranks", "4"], timeout=900)
    t0 = time.time()
    check(phase_a(stack.base)["texts"] == a["texts"],
          "a replica answered differently from one chip")
    # distinct prompts, all in flight at once: nothing for the KV router
    # to match, so it spreads them by load — every replica must decode
    from concurrent.futures import ThreadPoolExecutor

    prompts = [f"request number {i}: " + "abcdefgh"[i % 8] * 40
               for i in range(16)]
    with ThreadPoolExecutor(len(prompts)) as pool:
        first = list(pool.map(lambda p: chat(stack.base, p, 8)["text"],
                              prompts))
        again = list(pool.map(lambda p: chat(stack.base, p, 8)["text"],
                              prompts))
    check(first == again, "replicas disagree on the same greedy prompt")
    st, body = http(f"http://127.0.0.1:{stack.status_port}/events.json")
    check(st == 200, f"/events.json answered {st}")
    decoded = {rank: sum(e["kind"] == "decode_block" for e in d["events"])
               for rank, d in sorted(json.loads(body).items())}
    check(len(decoded) == 4 and all(decoded.values()),
          f"not every replica decoded: {decoded}")
    rep = stack.report()
    used = [m["bytes_in_use"] or 0 for m in rep["memory"]]
    say(phase="dp-ranks4", seconds=round(time.time() - t0, 1),
        load_seconds=round(secs, 1), **compile_line(rep),
        bytes_in_use=used, decode_blocks_per_replica=decoded,
        router_mode="kv")
    if not args.rehearse_cpu:
        # every replica's parameters and KV pool on its own device
        floor = args.min_replica_bytes
        check(len(used) == 4 and all(u >= floor for u in used),
              f"replicas do not each hold {floor} B on their own chip: {used}")
    stack.stop_worker()
    return device


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the four-chip phases, and only those")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the checkpoint and the prompts")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny model on the CPU backend, to rehearse the "
                         "control flow; never prints the result line")
    ap.add_argument("--log-dir",
                    default=os.path.join(ROOT, "chiprun_out", "chip_smoke"))
    args = ap.parse_args()

    t_start = time.time()
    env = dict(os.environ, PYTHONPATH=ROOT)
    hf_cfg, pages = LLAMA_3_2_1B, KV_PAGES
    worker_flags = []
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        hf_cfg, pages = TINY, 1024
        worker_flags = ["--platform", "cpu", "--dtype", "float32"]
    elif args.chips == 4:
        pages = 4096  # 2 GiB a replica: the four-chip phases move no big pool
    worker_flags += ["--num-pages", str(pages)]
    c = hf_cfg
    n_params = c["vocab_size"] * c["hidden_size"] + c["num_hidden_layers"] * (
        2 * c["hidden_size"] * c["head_dim"] * (
            c["num_attention_heads"] + c["num_key_value_heads"])
        + 3 * c["hidden_size"] * c["intermediate_size"])
    pool_bytes = pages * (2 * c["num_hidden_layers"] * 16
                          * c["num_key_value_heads"] * c["head_dim"] * 2)
    # weights + pool of one replica, less a tenth: what each of the four
    # devices must hold under --dp-ranks 4
    args.min_replica_bytes = int(0.9 * (2 * n_params + pool_bytes))

    os.makedirs(args.log_dir, exist_ok=True)
    stack = None
    try:
        sys.path.insert(0, ROOT)
        probed = probe_device(env)
        check_device(probed, args.chips, args.rehearse_cpu)
        ckpt = os.path.join(
            WORK, f"{'tiny' if args.rehearse_cpu else 'llama-3.2-1b'}"
                  f"-seed{args.seed}")
        t0 = time.time()
        fresh = make_checkpoint(ckpt, hf_cfg, args.seed)
        say(phase="checkpoint", path=os.path.relpath(ckpt, ROOT),
            written=fresh, seconds=round(time.time() - t0, 1),
            params=n_params, kv_pool_bytes=pool_bytes,
            device_probe=probed)
        stack = Stack(args.log_dir, env,
                      router_mode="kv" if args.chips == 4 else "round_robin")
        run = run_four_chips if args.chips == 4 else run_one_chip
        device = run(stack, ckpt, args, worker_flags)
    except (SmokeFailure, SystemExit) as e:
        print(f"CHIP SMOKE FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if stack is not None:
            stack.close()
    say(phase="total", seconds=round(time.time() - t_start, 1))
    if args.rehearse_cpu:
        print("rehearsal finished: every phase passed on the CPU backend; "
              "this is not a chip run", file=sys.stderr)
        return 2
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
