"""Write a configuration's checkpoint directory: config.json, one streamed
model.safetensors of random bf16 weights from `weights_seed`, and the
program's test tokenizer.  Run as a child (`python checkpoint.py <config
file> <out dir>`): it imports numpy and the program's tokenizer helper, and
the benchmark's parent stays off both.

Weights are bf16 bit patterns: random sign and mantissa, exponent in
2^-9..2^-6 (zero mean, std about 0.014) — chip_smoke.py's recipe (PR 22),
under which activations keep a sane scale through the depth.  Biases get the
same draw; norm scales are ones.  Each tensor's stream is seeded by
(weights_seed, tensor name), so the file is the same whatever the order."""

import hashlib
import importlib.util
import json
import os
import struct
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHUNK = 1 << 25  # values per draw: 64 MiB of uint16


def load_module(kind_dir, name):
    """A file found by name: benchmark/<kind_dir>/<name>.py."""
    path = os.path.join(BENCH, kind_dir, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind_dir}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def file_sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def checkpoint_key(config):
    """What the checkpoint depends on: the model object, the seed, and the
    code that lays it out and draws it."""
    h = hashlib.sha256()
    h.update(json.dumps([config["model"], config["weights_seed"]],
                        sort_keys=True).encode())
    h.update(file_sha(os.path.join(
        BENCH, "checkpoints", config["checkpoint"] + ".py")).encode())
    h.update(file_sha(os.path.abspath(__file__)).encode())
    return h.hexdigest()[:16]


def draw_bits(np, rng, n):
    r = rng.integers(0, 1 << 16, size=n, dtype=np.uint16)
    return (r & 0x807F) | ((118 + ((r >> 7) & 3)) << 7).astype(np.uint16)


def write(config, out_dir):
    import numpy as np

    layout = load_module("checkpoints", config["checkpoint"])
    model, seed = config["model"], config["weights_seed"]
    os.makedirs(out_dir, exist_ok=True)
    header, offset, plan = {}, 0, []
    for name, shape, kind in layout.tensors(model):
        n = 1
        for d in shape:
            n *= d
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [offset, offset + 2 * n]}
        plan.append((name, n, kind))
        offset += 2 * n
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    tmp = os.path.join(out_dir, "model.safetensors.tmp")
    with open(tmp, "wb") as f:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for name, n, kind in plan:
            if kind == "ones":
                f.write(np.full(n, 0x3F80, np.uint16).tobytes())
                continue
            tseed = int.from_bytes(hashlib.sha256(
                f"{seed}:{name}".encode()).digest()[:8], "little")
            rng = np.random.default_rng(tseed)
            for start in range(0, n, CHUNK):
                f.write(draw_bits(np, rng, min(CHUNK, n - start)).tobytes())
    os.replace(tmp, os.path.join(out_dir, "model.safetensors"))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(model, f)
    sys.path.insert(0, os.path.dirname(BENCH))
    from dynamo_tpu.testing import tiny_tokenizer

    with open(os.path.join(out_dir, "tokenizer.json"), "w") as f:
        f.write(tiny_tokenizer().to_json_str())
    with open(os.path.join(out_dir, "tokenizer_config.json"), "w") as f:
        json.dump({"eos_token": "<|endoftext|>"}, f)
    with open(os.path.join(out_dir, ".complete"), "w") as f:
        f.write(checkpoint_key(config))
    return offset


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    print(json.dumps({"bytes": write(cfg, sys.argv[2])}))
