"""One Mamba-2 layer's convolution, blocked scan and gated norm alone (the
ops of `models.hybrid._mamba` under `ssm.conv`, `ssm.scan` and
`ssm.gate_norm`, what `ssm_scan_floor_s` counts) at a configuration's
widths, timed on the chip this process holds: `--reps` layers in ONE program
(a `fori_loop` over a small stack of layers; a layer's output is the next
one's gate, its states go to a pool of slots the next one reads), median of
`--runs` calls, divided by the repetitions.  One JSON line per (rows, tokens,
form):

  form "xla":    `ops.ssm.scan_blocks`, the blocks as plain `jnp`;
  form "kernel": `ops.pallas_ssm.scan_pallas`, ONE kernel a layer, the gated
                 norm in its epilogue, its blocks ending where a state is
                 handed out (`scan_block`: the model's block, or a page of a
                 short row);
  form "rule":   `ops.ssm.scan`, whichever of the two the served path takes
                 at these shapes (`path`: "pallas" | "xla").

States are handed out where the served path hands them out at that shape
(`models.hybrid._inside`).  `floor_pct` is the family's `ssm_scan_floor_s`
(benchmark/roofline/<family>.py) for ONE layer as a share of the measured
time.  `--only scan` times the scan without the convolution and the norm
(`floor_pct` then still holds all three).  Each form's line also says how far
its y and its last state lie from the same blocks computed in float32 at the
backend's highest precision (`y_err`, `h_err`, beside the largest |y| and
|h|): the kernel must lie no further than "xla" does.

    python scripts/time_ssm_scan.py benchmark/configs/<config>.json \\
        [--tokens 64,128,256,512] [--rows 1,4] [--forms xla,kernel]

`--aot` compiles each program for a DESCRIBED v5e instead (no chip, nothing
runs) and prints its temporaries, the kernels' names and the float32 arrays
of the loop's body that end in [heads, Q, Q] or [groups, Q, Q].  Single
process, no children; a measurement needs a TPU (refuses the CPU).  Where
`pallas_ssm.MIN_SCAN_BLOCK` is measured.  `--ops N` traces one more call and
lists the N device ops that took most of it (microseconds a layer)."""

import argparse
import importlib.util
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PAGE = 16


def device_ops(jax, call, reps, top):
    """[(microseconds a layer, the op's HLO line cut short)] of the `top`
    device ops that took most of one traced call, the loop itself apart."""
    import glob
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        call()
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        planes = jax.profiler.ProfileData.from_file(path).planes
        spent = {}
        for plane in planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    if not ev.name.startswith("%while"):
                        spent[ev.name] = spent.get(ev.name, 0) + ev.duration_ns
    return [(round(ns / 1e3 / reps, 2), name[:110]) for name, ns in sorted(
        spent.items(), key=lambda kv: -kv[1])[:top]]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--tokens", default="64,128,256,512")
    ap.add_argument("--rows", default="1,4")
    ap.add_argument("--forms", default="xla,kernel")
    ap.add_argument("--only", default="", choices=["", "scan"])
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--reps", type=int, default=48)
    ap.add_argument("--runs", type=int, default=9)
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args()

    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.analysis import xla_ledger
    from dynamo_tpu.models import ModelConfig, hybrid
    from dynamo_tpu.ops import pallas_moe, pallas_ssm, ssm

    with open(args.config) as f:
        run = json.load(f)
    cfg = ModelConfig.from_hf_config(run["model"])
    if not cfg.ssm_heads or cfg.ssm_dt_rank:
        raise SystemExit(f"{run['name']} has no Mamba-2 layer")
    d, cd, nh, hp = (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads,
                     cfg.ssm_head_dim)
    G, N, K, L = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv_kernel, args.layers
    spec = importlib.util.spec_from_file_location(
        "family", os.path.join(ROOT, "benchmark", "roofline",
                               run["roofline"] + ".py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    with open(os.path.join(ROOT, "benchmark", "peaks",
                           "TPU_v5_lite.json")) as f:
        peaks = json.load(f)
    depth = (run["model"]["hybrid_override_pattern"].count("M")
             if "hybrid_override_pattern" in run["model"]
             else run["model"]["num_hidden_layers"])

    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        device = "described v5e"
    else:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SystemExit(f"no TPU here ({dev.platform}): nothing to time")
        sharding, device = None, dev.device_kind

    bf16, f32 = jnp.bfloat16, jnp.float32
    stack_shapes = {"conv_w": ((L, K, cd), bf16), "conv_b": ((L, cd), bf16),
                    "dt_bias": ((L, nh), f32), "A_log": ((L, nh), f32),
                    "D": ((L, nh), f32), "gate_norm": ((L, d), bf16)}

    def scan_of(form, at):
        """(x, dt, A, Bm, Cm, D, h0, gate) -> (y [B, S, d], h, the states
        after `at`), y gated and normed where `gate` is given."""
        if form == "rule":
            return lambda *a, gate: ssm.scan(*a, cfg.ssm_chunk, at, gate=gate)

        def xla(x, *rest, gate):
            y, h, hs = ssm.scan_blocks(x, *rest, cfg.ssm_chunk, at)
            y = y.reshape(*x.shape[:2], d)
            if gate is not None:
                with jax.named_scope("ssm.gate_norm"):
                    y = ssm.gate_norm(y, gate[0], gate[1], G, gate[2])
            return y, h, hs

        def kernel(x, *rest, gate):
            block = pallas_ssm.scan_block(x.shape[1], cfg.ssm_chunk, at)
            y, hs = pallas_ssm.scan_pallas(
                x, *rest, block, gate and gate[:2], gate and gate[2],
                interpret=bool(pallas_moe.single_device(x)))
            return y, hs[-1], [hs[t // block - 1] for t in at]

        return xla if form == "xla" else kernel

    def layer(form, lp, xbc, z, dt, window, h0, at):
        """`hybrid._mamba` between its two products -> (y, window', the
        states handed out, the last one last)."""
        B, S, _ = xbc.shape
        lens = jnp.full((B,), S, jnp.int32)
        gate = None
        if args.only != "scan":
            gate = (z, lp["gate_norm"], cfg.rms_norm_eps)
            with jax.named_scope("ssm.conv"):
                xbc, window, _ = ssm.conv(xbc, window, lp["conv_w"],
                                          lp["conv_b"], lens, at)
        with jax.named_scope("ssm.scan"):
            step = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"])
            y, h, hs = scan_of(form, at)(
                xbc[..., :d].reshape(B, S, nh, hp), step,
                -jnp.exp(lp["A_log"]),
                xbc[..., d:d + G * N].reshape(B, S, G, N),
                xbc[..., d + G * N:].reshape(B, S, G, N), lp["D"], h0,
                gate=gate)
        return y.reshape(B, S, d), window, [*hs, h]

    def program(form, at):
        def layers(stack, xbc, z, dt, window, pool):
            def one(i, carry):
                z, window, pool = carry
                lp = {k: jax.lax.dynamic_index_in_dim(v, i % L, 0, False)
                      for k, v in stack.items()}
                y, window, hs = layer(form, lp, xbc, z, dt, window,
                                      pool[-1], at)
                for j, h in enumerate(hs):  # to their slots, as a step's do
                    pool = pool.at[len(pool) - len(hs) + j].set(h)
                return y, window, pool

            return jax.lax.fori_loop(0, args.reps, one, (z, window, pool))

        return jax.jit(layers)

    def drawn(B, S):
        ks = jax.random.split(jax.random.PRNGKey(0), 10)
        stack = {
            "conv_w": jax.random.normal(ks[0], (L, K, cd), f32) * 0.5,
            "conv_b": jax.random.normal(ks[1], (L, cd), f32) * 0.1,
            "dt_bias": jax.random.uniform(ks[2], (L, nh), f32, -4.0, -1.0),
            "A_log": jnp.log(jax.random.uniform(ks[3], (L, nh), f32, 1., 16.)),
            "D": jnp.ones((L, nh), f32),
            "gate_norm": jnp.ones((L, d), f32)}
        stack = {k: v.astype(stack_shapes[k][1]) for k, v in stack.items()}
        return stack, (
            jax.random.normal(ks[4], (B, S, cd), f32).astype(bf16),
            jax.random.normal(ks[5], (B, S, d), f32).astype(bf16),
            jax.random.normal(ks[6], (B, S, nh), f32).astype(bf16),
            jnp.zeros((B, K - 1, cd), bf16),
            jax.random.normal(ks[7], (hybrid.SNAP_COLS + 1, B, nh, hp, N),
                              f32))

    def once(form, at, up=lambda a: a):
        """One layer of the stack over the drawn operands, jitted: y and the
        last state."""
        def fn(stack, xbc, z, dt, window, pool):
            lp = {k: up(v[0]) for k, v in stack.items()}
            y, _, hs = layer(form, lp, up(xbc), up(z), dt, up(window),
                             pool[-1], at)
            return y.astype(f32), hs[-1]

        return jax.jit(fn)

    def exact(stack, ops, at):
        """The same from the same blocks in float32 at the backend's highest
        precision."""
        with jax.default_matmul_precision("highest"):
            return once("xla", at, lambda a: a.astype(f32))(stack, *ops)

    for B in map(int, args.rows.split(",")):
        for S in map(int, args.tokens.split(",")):
            at = hybrid._inside(cfg, S, PAGE)
            floor_ms = family.ssm_scan_floor_s(
                run["model"], peaks, B * S, B)[0] / depth * 1e3
            shapes = [{k: jax.ShapeDtypeStruct(s, t, sharding=sharding)
                       for k, (s, t) in stack_shapes.items()}] + [
                jax.ShapeDtypeStruct(s, t, sharding=sharding)
                for s, t in (((B, S, cd), bf16), ((B, S, d), bf16),
                             ((B, S, nh), bf16), ((B, K - 1, cd), bf16),
                             ((hybrid.SNAP_COLS + 1, B, nh, hp, N), f32))]
            if not args.aot:
                stack, ops = drawn(B, S)
                want = exact(stack, ops, at)
            for form in args.forms.split(","):
                line = {"config": run["name"], "rows": B, "tokens": S,
                        "form": form, "handed_out_at": list(at),
                        "block": pallas_ssm.scan_block(S, cfg.ssm_chunk, at),
                        "device": device, "floor_ms_a_layer": floor_ms}
                if args.only:
                    line["only"] = args.only
                try:
                    fn = program(form, at)
                    if args.aot:
                        with pallas_moe.checked(interpret=False):
                            compiled = fn.lower(*shapes).compile()
                        text = compiled.as_text()
                        line.update(
                            temp_mb=round(compiled.memory_analysis(
                            ).temp_size_in_bytes / 2 ** 20, 1),
                            kernels=re.findall(
                                r"(%[\w.]+) = [^\n]*custom_call_target="
                                r"\"tpu_custom_call\"", text),
                            scores=sorted(set(re.findall(
                                r"f32\[(?:\d+,)?(?:%d|%d),(\d+),\1\]"
                                % (nh, G), text))))
                    else:
                        y, h = once(form, at)(stack, *ops)
                        line.update(
                            y_err=float(jnp.abs(y - want[0]).max()),
                            y_max=float(jnp.abs(want[0]).max()),
                            h_err=float(jnp.abs(h - want[1]).max()),
                            h_max=float(jnp.abs(want[1]).max()))
                        times = []
                        for i in range(args.runs + 2):
                            t0 = time.perf_counter()
                            jax.block_until_ready(fn(stack, *ops))
                            if i >= 2:
                                times.append(
                                    (time.perf_counter() - t0) * 1e3)
                        ms = statistics.median(times) / args.reps
                        line.update(
                            ms_a_layer=ms,
                            ms_a_layer_min=min(times) / args.reps,
                            floor_pct=100 * floor_ms / ms, runs=len(times),
                            reps=args.reps)
                        if args.ops:
                            line["ops_us_a_layer"] = device_ops(
                                jax, lambda: jax.block_until_ready(
                                    fn(stack, *ops)), args.reps, args.ops)
                    if form == "rule":
                        line["path"] = xla_ledger.path_choice(
                            "ssm_scan", rows=B, chunk=S)
                except Exception as e:  # noqa: BLE001 — a form that is refused
                    line["error"] = str(e)[-1500:]
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
