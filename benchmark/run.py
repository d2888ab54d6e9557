#!/usr/bin/env python3
"""One run of one benchmark cell on the served path.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(benchmark/configs/<config>.json, found through the entry's `file`) under a
traffic mix (benchmark/traffic/<mix>.json), with the sizes of its own, if it
has any, in benchmark/cells/<cell>.json.  This parent never imports jax.
It writes the configuration's checkpoint once per checkout, starts
`python -m dynamo_tpu.runtime`, `python -m dynamo_tpu.worker --model <dir>`
and `python -m dynamo_tpu.frontend` as children (the worker alone on the
chip), checks the served logprobs against the plain reference, lets the
mix's loop (benchmark/loops/<loop>.py) warm up and measure `--seconds` of
real HTTP traffic against the frontend, stops every child, and prints the
result as the LAST line of its standard output.

Earlier lines are JSON notes ({"note": ...}) for a reader; see README.md.
No TPU, an unknown device kind, a dead child before the window, a
configuration the program cannot load or a broken harness: exit code 1, one
`BENCHMARK RUN FAILED:` line and no result line.  Every child is started
through one `lib/procs.py` `ProcSet`, and none outlives this process,
whether it ends, fails, is signalled or is killed.  `--rehearse-cpu` walks
the same control flow on the CPU backend with the tiny cells of
benchmark/tests/data/REHEARSAL.json; it never prints a result line and
exits 2.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import glob  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import checkpoint as ckpt  # noqa: E402
from lib import collect, loadgen, probes, procs, roofline, stats  # noqa: E402
from lib import traffic  # noqa: E402
from lib import peaks as peaks_table  # noqa: E402
from lib import trace as trace_lib  # noqa: E402
from lib.procs import RunFailure  # noqa: E402

CACHE = os.path.join(BENCH, ".cache")  # git-ignored; made from seeds

# Policy stays the program's: a configuration or a cell passes the worker
# sizes only.  The context, `--max-model-len`, is a size: how long a prompt
# the traffic sends is the cell's, not the program's, to say.
POLICY_FLAGS = (
    "--decode-steps", "--decode-chain", "--decode-continuous",
    "--decode-block-ladder", "--mixed-prefill-tokens",
    "--prefill-chunk-tokens", "--attention-impl", "--fuse-projections",
    "--max-num-seqs", "--max-prefill-tokens",
    "--speculative-ngram-k", "--quantization", "--no-prefix-caching")
# the worker's own default (dynamo_tpu/worker/__main__.py, `--max-model-len`):
# what a cell that names no context is served at
DEFAULT_MAX_MODEL_LEN = 4096


def note(what, **fields):
    print(json.dumps({"note": what, **fields}), flush=True)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise RunFailure(f"no {what} named {name!r}")


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


# -- set-up: checkpoint, reference, stack --------------------------------------- #

def child_env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update(extra)
    return env


def family_files(config):
    """What the configuration's family brings, each found by name; a missing
    one fails the run here, before any child, and not after the window."""
    for kind_dir, name in (("checkpoints", config["checkpoint"]),
                           ("reference", config["reference"])):
        if not os.path.exists(os.path.join(BENCH, kind_dir, name + ".py")):
            raise RunFailure(f"{config['name']}: its family has no "
                             f"benchmark/{kind_dir}/{name}.py")
    roofline.family(config)


def require_tpu(ps, chips):
    """Asked of a child that exits before any worker starts; only a run
    that has to write the checkpoint pays for it (later runs learn the
    device from the worker's DEVICE line)."""
    code = ("import json, jax; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'count': len(d)}))")
    rc, out, err = ps.run([sys.executable, "-c", code], "device-probe",
                          {"JAX_PLATFORMS": "tpu"}, timeout=300)
    if rc != 0:
        raise RunFailure("JAX finds no TPU here: " + err[-400:])
    found = json.loads(out.strip().splitlines()[-1])
    if found["platform"] != "tpu" or found["count"] < chips:
        raise RunFailure(f"this cell needs {chips} TPU chip(s); JAX finds "
                         f"{found}")


def ensure_checkpoint(ps, config, config_path, chips, rehearse):
    path = os.path.join(CACHE, "ckpt",
                        f"{config['name']}-{ckpt.checkpoint_key(config)}")
    done = os.path.join(path, ".complete")
    if os.path.exists(done):
        return path, 0.0
    if not rehearse:
        require_tpu(ps, chips)
    t0 = time.monotonic()
    rc, _, err = ps.run(
        [sys.executable, os.path.join(BENCH, "lib", "checkpoint.py"),
         config_path, path], "checkpoint-writer", {"JAX_PLATFORMS": "cpu"},
        timeout=1800)
    if rc != 0:
        raise RunFailure("checkpoint writer failed: " + err[-1500:])
    return path, time.monotonic() - t0


def start_reference(ps, config, config_path, ckpt_dir, depth, lens):
    """The plain reference's answers to the probes: cached in the checkout,
    else computed by a child on the host CPU while the worker loads.  The
    child writes the file by `os.replace` at its end, so a child that is
    stopped with a failed run leaves nothing a later run would trust."""
    h = hashlib.sha256(
        f"{os.path.basename(ckpt_dir)}:{depth}:{list(lens)}".encode())
    for f in (os.path.join(BENCH, "reference", config["reference"] + ".py"),
              os.path.join(BENCH, "lib", "probes.py"),
              os.path.join(BENCH, "lib", "reference_child.py")):
        h.update(ckpt.file_sha(f).encode())
    out = os.path.join(CACHE, "reference",
                       f"{config['name']}-{h.hexdigest()[:16]}.json")
    if os.path.exists(out):
        return out, None
    child = ps.spawn(
        [sys.executable, os.path.join(BENCH, "lib", "reference_child.py"),
         config_path, ckpt_dir, out, str(depth), json.dumps(list(lens))],
        "reference", out + ".log",
        {"JAX_PLATFORMS": "cpu"})
    return out, child


def cell_sizes(cell, rehearse):
    """The cell's own file, benchmark/cells/<cell>.json (a rehearsal's:
    tests/data/cells/), or {}: sizes that belong to a configuration under
    ONE mix, so that the configuration's file goes on serving its other
    cells at the flags it has.  `worker_flags` there are laid over the
    configuration's."""
    path = os.path.join(BENCH, *(("tests", "data") if rehearse else ()),
                        "cells", cell["name"] + ".json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)


def sized_flags(cell, config, sizes):
    """{flag: value}: the configuration's `worker_flags` in their order, the
    cell's laid over them (a flag both name keeps its place and takes the
    cell's value).  A policy flag on either is refused."""
    merged = {}
    for owner, flags in (
            (f"configuration {config['name']}", config.get("worker_flags", {})),
            (f"cell {cell['name']} (benchmark/cells/{cell['name']}.json)",
             sizes.get("worker_flags", {}))):
        for flag, value in flags.items():
            if flag in POLICY_FLAGS:
                raise RunFailure(
                    f"{owner}: worker_flags {flag} is policy, not a size; "
                    "the cells measure the worker at its defaults")
            merged[flag] = value
    return merged


def worker_flags(cell, config, sizes, rehearse):
    flags = []
    for flag, value in sized_flags(cell, config, sizes).items():
        flags += [flag, str(value)]
    if rehearse:
        flags += ["--platform", "cpu", "--dtype", "float32"]
    return flags


def check_context(cell, config, sizes, mix):
    """Before any child: the worker's context holds the longest request the
    mix CAN draw and the longest probe, and the model's positions hold the
    context.  Returns the context."""
    flags = sized_flags(cell, config, sizes)
    context = int(flags.get("--max-model-len", DEFAULT_MAX_MODEL_LEN))
    where = ("worker_flags --max-model-len" if "--max-model-len" in flags
             else "the worker's default --max-model-len")
    limit = config["model"].get("max_position_embeddings")
    if limit is not None and context > int(limit):
        raise RunFailure(
            f"cell {cell['name']}: {where} {context} is past the model's "
            f"max_position_embeddings {limit}")
    for key, need in (
            ("prefix_len + fresh_len + output_len",
             traffic.longest_request(mix)),
            ("probe_lens", probes.longest_request(probes.lens_of(mix)))):
        if need > context:
            raise RunFailure(
                f"cell {cell['name']}: mix {cell['traffic']} can send "
                f"{need} tokens ({key}), past {where} {context}")
    return context


def check_device(device, chips, rehearse):
    if rehearse:
        return None
    if device["platform"] != "tpu":
        raise RunFailure(f"the worker runs on {device['platform']!r}, not a "
                         "TPU: no CPU time is written under a metric's name")
    if device["count"] < chips:
        raise RunFailure(f"the cell needs {chips} chips, the worker sees "
                         f"{device['count']}")
    try:
        return peaks_table.peaks_for(device["kind"])
    except KeyError as e:
        raise RunFailure(str(e)) from None


# -- correctness ---------------------------------------------------------------- #

async def run_probes(stack, config, ref_path):
    """(correct, detail): see lib/probes.py."""
    import aiohttp

    with open(ref_path) as f:
        ref = json.load(f)
    lens = tuple(ref["probe_lens"])
    texts = probes.probe_texts(config["weights_seed"],
                               tuple(config["prompt_vocab"]), lens)

    async def ask(http, prompt, n):
        body = loadgen.completion_body(
            stack.MODEL_NAME, {"prompt": prompt, "max_tokens": n},
            logprobs=0, stream=False)
        async with http.post(stack.base + "/v1/completions",
                             json=body) as resp:
            if resp.status != 200:
                raise RunFailure(f"probe answered {resp.status}: "
                                 f"{(await resp.text())[:300]}")
            r = await resp.json()
        choice = r["choices"][0]
        if (choice["finish_reason"] != "length"
                or r["usage"]["completion_tokens"] != n
                or r["usage"]["prompt_tokens"] != len(prompt)):
            raise RunFailure(f"probe: {choice['finish_reason']} {r['usage']}")
        return list(choice["logprobs"]["token_logprobs"])

    tol, margin = ref["tolerance"], ref["tie_margin"]
    forced, greedy = [], []
    async with aiohttp.ClientSession() as http:
        for text, n in zip(texts, lens):
            forced.append([(await ask(http, text[:n + k], 1))[0]
                           for k in range(probes.PROBE_STEPS)])
        for i in ref.get("greedy_probes", ()):
            greedy.append(await ask(http, texts[i][:lens[i]], ref["depth"]))
    ok, detail = probes.compare_forced(forced, ref["forced"], tol, margin)
    out = {"tolerance": tol, "tie_margin": margin, "probe_lens": list(lens),
           "forced_ok": ok, "forced": detail, "greedy_depth": ref["depth"]}
    if greedy:
        g_ok, out["greedy"] = probes.compare_greedy(greedy, ref["greedy"],
                                                    tol, margin)
        out["greedy_ok"] = g_ok
        ok = ok and g_ok
    out["compared"] = compared_numbers(out, len(lens) * probes.PROBE_STEPS)
    out["served"] = forced
    out["reference"] = [[round(s["logprob"], 4) for s in steps]
                        for steps in ref["forced"]]
    return ok, out


def compared_numbers(detail, steps_due):
    """Every number `correct` turns on, each beside its limit, under short
    plain names: the result line's last key and the run's last lines on
    standard error."""
    forced = detail["forced"]
    out = {
        "logprob_past_allowed_max": {
            "value": forced["max_past_allowed"], "limit": 0.0,
            "tolerance": detail["tolerance"]},
        "logprob_diff_max_agreeing": {
            "value": forced["max_abs_logprob_diff"],
            "limit": detail["tolerance"]},
        "forced_steps_over": {"value": sum(forced["steps_over_by_probe"]),
                              "limit": 0},
        "forced_steps_compared": {"value": forced["steps_compared"],
                                  "at_least": steps_due}}
    if "greedy" in detail:
        out["greedy_problems"] = {
            "value": len(detail["greedy"]["problems"]), "limit": 0}
    return out


# -- the measured phases -------------------------------------------------------- #

async def measure(stack, config, mix, args):
    """The mix's loop warms up, then measures the window."""
    try:
        loop = ckpt.load_module("loops", mix["loop"])
    except FileNotFoundError:
        raise RunFailure(f"no loop benchmark/loops/{mix['loop']}.py"
                         ) from None
    run = await loop.run({
        "url": stack.base + "/v1/completions", "model": stack.MODEL_NAME,
        "mix": mix, "seed": args.seed, "seconds": args.seconds,
        "vocab": tuple(config["prompt_vocab"]),
        "metrics_url": stack.status + "/metrics.json"})
    note("warmup", **run["warmup"])
    return run


# -- the run -------------------------------------------------------------------- #

def mix_path(cell, rehearse):
    """benchmark/traffic/<mix>.json; a rehearsal looks under tests/data/
    first, where a stand-in of a mix too long for the CPU may lie."""
    name = cell["traffic"] + ".json"
    tiny = os.path.join(BENCH, "tests", "data", "traffic", name)
    if rehearse and os.path.exists(tiny):
        return tiny
    return os.path.join(BENCH, "traffic", name)


def load_reader(kind_dir, metric_name):
    try:
        return ckpt.load_module(kind_dir, metric_name).read
    except FileNotFoundError:
        raise RunFailure(f"no reader benchmark/{kind_dir}/{metric_name}.py"
                         ) from None


def reduce_trace(ps, xprof_dir, run, log_dir):
    files = glob.glob(os.path.join(xprof_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise RunFailure(f"the worker wrote no profiler trace to {xprof_dir}")
    if run["clock_anchor"] is None:
        raise RunFailure("no clock anchor from /events.json")
    wall_ns, mono_ns = run["clock_anchor"]
    t0, t1 = int(run["t0"] * 1e9), int(run["t1"] * 1e9)
    try:
        compact = trace_lib.load_in_child(
            ps, max(files, key=os.path.getsize),
            os.path.join(log_dir, "trace.json"), wall_ns - mono_ns, t0, t1)
        reduced = trace_lib.reduce(compact, t0, t1, run["events"])
        note("trace",
             file_bytes=os.path.getsize(max(files, key=os.path.getsize)),
             captured_s=reduced["window_s"], asked_s=(t1 - t0) / 1e9,
             capture_ended_early=reduced["capture_ended_early"],
             lines_end_s={ln["name"]: round((max(
                 a + d for _, a, d in ln["events"]) - t0) / 1e9, 3)
                 for pl in compact["planes"] for ln in pl["lines"]
                 if ln["events"]},
             scopes=compact.get("scopes_note"), planes=compact["summary"])
        shutil.rmtree(xprof_dir, ignore_errors=True)  # hundreds of MB
        return reduced
    except RuntimeError as e:
        raise RunFailure(str(e)) from None


async def serve_and_measure(stack, cell, config, mix, sizes, args, ckpt_dir,
                            ref_path, ref_child, xprof_dir):
    env = {}
    if not args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "tpu"
    if args.trace:
        # the program's capture starts at the first non-idle engine step and
        # lasts N steps; N is set beyond any run, so the capture ends when
        # the worker shuts down, and the reduction cuts it to the window
        env.update(DYN_TPU_XPROF_STEPS=str(10 ** 9),
                   DYN_TPU_XPROF_DIR=xprof_dir)
    flags = worker_flags(cell, config, sizes, args.rehearse_cpu)
    try:
        device, secs = stack.start_worker(ckpt_dir, flags, env, timeout=900)
    except RunFailure as e:
        raise RunFailure(
            f"cell {cell['name']}, configuration {config['name']}: the "
            f"program's worker did not come up on it: {e}") from None
    peaks = check_device(device, cell["chips"], args.rehearse_cpu)
    note("worker", seconds_to_ready=secs, device=device, flags=flags)
    deadline = time.monotonic() + 60
    while True:
        models = await collect.get_json(stack.base + "/v1/models")
        if any(m["id"] == stack.MODEL_NAME for m in models["data"]):
            break
        if time.monotonic() > deadline:
            raise RunFailure("the frontend never listed the model")
        await asyncio.sleep(0.25)
    if ref_child is not None:
        t0 = time.monotonic()
        while ref_child.poll() is None:
            await asyncio.sleep(0.5)
        stack.ps.forget(ref_child)
        if ref_child.returncode != 0:
            raise RunFailure("the reference failed: "
                             + procs.log_tail(ref_path + ".log"))
        note("reference", waited_seconds=time.monotonic() - t0)
    correct, detail = await run_probes(stack, config, ref_path)
    note("probes", correct=correct, **detail)
    poller = collect.EventPoller(stack.status)
    if args.trace:
        poller.start()
    run = await measure(stack, config, mix, args)
    if args.trace:
        await poller.stop()
    run.update(correct=correct, compared=detail["compared"], device=device,
               peaks=peaks, config=config,
               mix=mix, events=poller.events, events_dropped=poller.dropped,
               clock_anchor=poller.anchor, trace=None)
    return run


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args()


def main():
    """Exit code 0 and a result line, or one failure line and exit code 1
    (a rehearsal: 2); by either way, and by a signal, with no child left."""
    args = parse_args()
    ps = procs.ProcSet(child_env())
    try:
        with ps.guard():
            return run_cell(args, ps)
    except RunFailure as e:
        print(f"BENCHMARK RUN FAILED: {e}", file=sys.stderr, flush=True)
    except Exception as e:  # a broken harness or reader is a run without a
        # result like any other; its traceback goes beside the logs
        os.makedirs(os.path.join(CACHE, "logs"), exist_ok=True)
        where = os.path.join(CACHE, "logs", "last_failure.txt")
        with open(where, "w") as f:
            traceback.print_exc(file=f)
        frame = traceback.extract_tb(e.__traceback__)[-1]
        print(f"BENCHMARK RUN FAILED: {type(e).__name__}: {e} at "
              f"{os.path.relpath(frame.filename, ROOT)}:{frame.lineno} "
              f"(traceback: {os.path.relpath(where, ROOT)})",
              file=sys.stderr, flush=True)
    return 1


def run_cell(args, ps):
    spec_path = (os.path.join(BENCH, "tests", "data", "REHEARSAL.json")
                 if args.rehearse_cpu else os.path.join(ROOT, "BENCHMARK.json"))
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        raise RunFailure("the system under test (dynamo_tpu/) is not in "
                         "this checkout")
    with open(spec_path) as f:
        spec = json.load(f)
    cell = by_name(spec["workloads"], args.workload, "workload")
    entry = by_name(spec["configs"], cell["config"], "configuration")
    config_path = os.path.join(ROOT, entry["file"])
    with open(config_path) as f:
        config = json.load(f)
    family_files(config)
    mix = traffic.load_mix(mix_path(cell, args.rehearse_cpu))
    sizes = cell_sizes(cell, args.rehearse_cpu)
    context = check_context(cell, config, sizes, mix)
    if args.seconds is None:
        args.seconds = float(spec["run_seconds"])
    log_dir = os.path.join(
        CACHE, "logs", f"{args.workload}-{args.seed}-{args.trace}")
    xprof_dir = os.path.join(log_dir, "xprof")
    shutil.rmtree(xprof_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)

    ckpt_dir, wrote = ensure_checkpoint(ps, config, config_path,
                                        cell["chips"], args.rehearse_cpu)
    note("checkpoint", path=os.path.relpath(ckpt_dir, ROOT),
         seconds_to_write=wrote)
    ref_path, ref_child = start_reference(
        ps, config, config_path, ckpt_dir,
        probes.greedy_depth(traffic.max_output_len(mix)), probes.lens_of(mix))
    stack = procs.Stack(ps, log_dir, config.get("router_mode", "round_robin"))
    run = asyncio.run(serve_and_measure(
        stack, cell, config, mix, sizes, args, ckpt_dir, ref_path, ref_child,
        xprof_dir))
    died = ps.dead()
    end_metrics = run["metrics1"]
    stack.stop_worker()  # the trace, when armed, is written now
    ps.stop()
    if args.trace:
        try:
            run["trace"] = reduce_trace(ps, xprof_dir, run, log_dir)
        except RunFailure as e:
            if not args.rehearse_cpu:  # the CPU backend has no device plane
                raise
            note("trace", rehearsal_without_device_plane=str(e))

    w = stats.window(run["records"], run["t0"], run["t1"])
    attempted, failed = len(w["measured"]), len(w["measured"]) - len(w["ok"])
    e2e = {"setup_s": run["t0"] - T_START}
    for m in spec["end_to_end"]:
        if m["name"] != "setup_s" and applies(m, args.workload):
            e2e[m["name"]] = load_reader("end_to_end", m["name"])(w)
    note("window", seconds=args.seconds, attempted=attempted, failed=failed,
         samples_ok=len(w["ok"]),
         errors=[r["error"] for r in w["measured"] if r["error"]][:3],
         ttft_tail=[[round(r["t_due"] - run["t0"], 2), r["prompt_len"],
                     round(stats.ttft_ms(r), 1)]
                    for r in sorted(w["ok"], key=stats.ttft_ms)[-16:]],
         requests_since_start=len(run["records"]),
         prompt_tokens_since_start=sum(r["prompt_len"]
                                       for r in run["records"]),
         kv_pool_tokens=sizes.get("memory", config.get("memory", {})).get(
             "kv_pool_tokens"),
         context_tokens=context,
         kv_usage_at_end=end_metrics.get("kv_usage"),
         events_dropped=run["events_dropped"], children_died=died)
    note("end_to_end", **e2e)  # every run shows them; only --trace 0 reports
    metrics = {}
    if args.trace:
        with open(os.path.join(log_dir, "events.json"), "w") as f:
            json.dump({"t0": run["t0"], "t1": run["t1"],
                       "events": run["events"]}, f)
        for m in spec["per_layer"]:
            if applies(m, args.workload):
                value = load_reader("layer_metrics", m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if applies(m, args.workload):
                if e2e.get(m["name"]) is None:
                    raise RunFailure(f"no {m['name']}: {failed} of "
                                     f"{attempted} requests failed")
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = dict(run["device"], memory_peak_bytes=max(
        m["peak_bytes_in_use"] or 0
        for m in end_metrics["runtime"]["memory"]))
    compared = dict(run["compared"],
                    children_died={"value": len(died), "limit": 0})
    result = {"correct": bool(run["correct"]) and not died,
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if run["trace"] is not None:
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    result["compared"] = compared  # last, as the check's record keeps ends
    if args.rehearse_cpu:
        note("rehearsal", would_print=result)
        print("rehearsal finished on the CPU backend; this is not a chip run",
              file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
