"""One half of a hyper-connection layer alone (`models.llama._residual`: the
mixer's read of the streams, a half that hands its input back scaled, the
write back) at a configuration's widths, timed on the chip this process
holds: `--reps` halves in ONE program (a `fori_loop` over a small stack of
mixers, each half's streams the next one's), median of `--runs` calls,
divided by the repetitions.  One JSON line per (tokens, form):

  form "xla":    the `jnp` forms of `ops/hyper_connections.py`, the streams
                 carried [T, n, h] as the program carried them before PR 58;
  form "kernel": the two Pallas kernels of `ops/pallas_hyper_connections.py`,
                 the streams carried [T, n x h];
  form "read" / "write": one of the two kernels alone, `--reps` times over.

`floor_pct` is `hyper_conn_floor_s` of `benchmark/roofline/xing4_0.py` for
one half ((2 n + 2) x hidden values a token in the served dtype over the HBM
peak) as a share of the measured time.  The second of "xla" and "kernel"
also says in how many values one half's two outputs differ (`differ`, of
`values`) and by how much at most (`diff`).

    python scripts/time_hyper_connections.py benchmark/configs/<config>.json \\
        [--tokens 64,256,512] [--forms xla,kernel] [--layers 3] [--reps 48]

`--aot` compiles each program for a DESCRIBED v5e instead (no chip, nothing
runs) and prints the ops of the loop's body whose result is as large as the
streams, and the kernels' names.  Single process, no children; a
measurement needs a TPU (refuses the CPU)."""

import argparse
import json
import math
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def stream_sized(text, values):
    """(op, result) of every op of the optimised HLO, fused computations'
    bodies left out, whose result holds `values` values: what is
    materialised at the size of the streams."""
    found, fused = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():
            fused = "fused_computation" in line.split("(")[0]
        m = re.search(r"(%[\w.-]+) = \(?(\w+)\[([\d,]+)\](\{[^ ]*\})? (\S+?)\(",
                      line)
        if not m or fused or m.group(5) in (
                "parameter", "bitcast", "get-tuple-element", "tuple",
                "while"):
            continue
        if math.prod(map(int, m.group(3).split(","))) == values:
            found.append(f"{m.group(1)} {m.group(2)}[{m.group(3)}]"
                         f"{m.group(4) or ''} {m.group(5)}")
    return found


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--tokens", default="64,256,512")
    ap.add_argument("--forms", default="xla,kernel")
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--reps", type=int, default=48)
    ap.add_argument("--runs", type=int, default=9)
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args()

    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import ModelConfig, llama
    from dynamo_tpu.ops import hyper_connections as hc
    from dynamo_tpu.ops import pallas_hyper_connections as pallas_hc
    from dynamo_tpu.ops import pallas_moe

    with open(args.config) as f:
        run = json.load(f)
    cfg = ModelConfig.from_hf_config(run["model"])
    n, h, M, L = cfg.hc_mult, cfg.hidden_size, cfg.hc_mixer_width, args.layers
    if not n:
        raise SystemExit(f"{run['name']} has no hyper-connections")
    with open(os.path.join(ROOT, "benchmark", "peaks",
                           "TPU_v5_lite.json")) as f:
        hbm = json.load(f)["hbm_bytes_per_s"]

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

    how = dict(iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
               clamp=cfg.hc_res_clamp, rms_eps=cfg.rms_norm_eps)
    shapes = {"hc_mlp_phi": (L, n, h, M), "hc_mlp_scale": (L, 3),
              "hc_mlp_base": (L, M)}

    def half(form, stack, x, at):
        """Half `at` of the stack over the streams x: [T, n, h] ("xla") or
        [T, n x h]; the half itself hands a tenth of its input back."""
        lp = {k: jax.lax.dynamic_index_in_dim(v, at, 0, False)
              for k, v in stack.items()}
        mixer = (lp["hc_mlp_phi"], lp["hc_mlp_scale"], lp["hc_mlp_base"])
        f = lambda u: ((u.astype(jnp.float32) * 0.1).astype(u.dtype),)  # noqa: E731
        if form == "xla":
            m = hc.mix(x, *mixer, **how)
            return hc.post(x, f(hc.pre(x, m.pre))[0], m.post, m.res)
        if form == "kernel":
            return llama._residual(cfg, lp, "hc_mlp", x, f)[0]
        with jax.named_scope("hc.mix"):
            u, w = pallas_hc.read(x, *mixer, **how)
        if form == "read":  # its outputs folded into a stream, to be kept
            return x.at[:, :h].add(u + w[:, :1].astype(u.dtype))
        with jax.named_scope("hc.post"):
            return pallas_hc.write(x, x[:, :h], w * 0.5, n=n)

    def weights_of(stack, streams):
        """How far the `read` kernel's weights lie from `mix`'s, in float32
        ulps of the largest magnitude, and in how many values `u` and X'
        differ from `pre` and `post` GIVEN the kernel's weights."""
        T = streams.shape[0]
        mixer = tuple(stack[k][0] for k in shapes)
        x = streams.reshape(T, n * h)
        u, w = jax.jit(lambda x: pallas_hc.read(x, *mixer, **how))(x)
        m = jax.jit(lambda s: hc.mix(s, *mixer, **how))(streams)
        logits = jax.jit(lambda s: hc._mix_logits(
            s, mixer[0], cfg.rms_norm_eps))(streams)
        pre, post, res, err, lg = pallas_hc.columns(n)
        eps = float(jnp.finfo(jnp.float32).eps)

        def ulps(got, want):
            return float(jnp.abs(got - want).max()
                         / (eps * jnp.maximum(jnp.abs(want).max(), 1e-30)))

        y = (u.astype(jnp.float32) * 0.1).astype(u.dtype)
        out = jax.jit(lambda x, y, w: pallas_hc.write(x, y, w, n=n))(x, y, w)
        want_u = jax.jit(hc.pre)(streams, w[:, pre])
        want_out = jax.jit(hc.post)(streams, y, w[:, post],
                                    w[:, res].reshape(T, n, n))
        return {"ulps": {"logits": ulps(w[:, lg].T, logits),
                         "pre": ulps(w[:, pre], m.pre),
                         "post": ulps(w[:, post], m.post),
                         "res": ulps(w[:, res].reshape(T, n, n), m.res),
                         "err": ulps(w[:, err], m.err)},
                "u_differ": int((u != want_u).sum()),
                "post_differ": int((out.reshape(T, n, h) != want_out).sum())}

    def program(form):
        def halves(stack, x):
            return jax.lax.fori_loop(
                0, args.reps, lambda i, x: half(form, stack, x, i % L), x)

        return jax.jit(halves)

    for T in map(int, args.tokens.split(",")):
        floor_ms = 2 * (2 * n + 2) * h * T / hbm * 1e3
        stack_shapes = {k: jax.ShapeDtypeStruct(v, jnp.float32,
                                                sharding=sharding)
                        for k, v in shapes.items()}
        if not args.aot:
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            stack = {
                "hc_mlp_phi": jax.random.normal(keys[0], shapes["hc_mlp_phi"],
                                                jnp.float32) * 0.014,
                "hc_mlp_scale": jnp.ones(shapes["hc_mlp_scale"], jnp.float32),
                "hc_mlp_base": jax.random.normal(
                    keys[1], shapes["hc_mlp_base"], jnp.float32) * 0.014}
            streams = jax.random.normal(keys[2], (T, n, h), jnp.bfloat16)
        outs = {}
        for form in args.forms.split(","):
            shape = (T, n, h) if form == "xla" else (T, n * h)
            line = {"config": run["name"], "tokens": T, "streams": n,
                    "hidden": h, "form": form, "device": device,
                    "floor_ms_a_half": floor_ms}
            try:
                fn = program(form)
                if args.aot:
                    with pallas_moe.checked(interpret=False):
                        compiled = fn.lower(
                            stack_shapes, jax.ShapeDtypeStruct(
                                shape, jnp.bfloat16,
                                sharding=sharding)).compile()
                    text = compiled.as_text()
                    line.update(
                        temp_mb=round(compiled.memory_analysis(
                        ).temp_size_in_bytes / 2 ** 20, 1),
                        stream_sized=stream_sized(text, T * n * h),
                        kernels=re.findall(
                            r"(%[\w.]+) = [^\n]*custom_call_target="
                            r"\"tpu_custom_call\"", text))
                else:
                    x = streams.reshape(shape)
                    if form in ("xla", "kernel"):  # one half's outputs
                        outs[form] = jax.jit(
                            lambda s, x, form=form: half(form, s, x, 0))(
                                stack, x).astype(jnp.float32).reshape(T, -1)
                        if len(outs) == 2:
                            d = jnp.abs(outs["kernel"] - outs["xla"])
                            line.update(diff=float(d.max()),
                                        differ=int((d > 0).sum()),
                                        values=int(d.size))
                        if form == "kernel":
                            line.update(weights_of(stack, streams))
                    times = []
                    for i in range(args.runs + 2):
                        t0 = time.perf_counter()
                        fn(stack, x).block_until_ready()
                        if i >= 2:
                            times.append((time.perf_counter() - t0) * 1e3)
                    ms = statistics.median(times) / args.reps
                    line.update(
                        ms_a_half=ms, ms_a_half_min=min(times) / args.reps,
                        floor_pct=100 * floor_ms / ms, runs=len(times),
                        reps=args.reps)
            except Exception as e:  # noqa: BLE001 — a form that is refused
                line["error"] = str(e)[-1500:]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
