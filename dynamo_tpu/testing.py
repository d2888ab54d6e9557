"""Test helpers: local cluster context managers (the analog of the
reference's EtcdServer/NatsServer ManagedProcess fixtures,
/root/reference/tests/conftest.py:195-236 — here everything runs in-process
on ephemeral ports)."""

from __future__ import annotations

import collections
import contextlib
import sys
from typing import AsyncIterator

from .runtime import (
    ControlPlaneClient,
    ControlPlaneServer,
    DistributedRuntime,
)


class counted_calls:
    """What a hot path DOES, counted where a clock would time it: every
    Python-level call this thread makes inside the block (a Python frame
    entered, or a C function or method called: `sys.setprofile`'s `call`
    and `c_call` events), as `total` and, by qualified name, `names`.

        with counted_calls() as c:
            for _ in range(n):
                rec.record("decode_block", rung=8)
        assert c.total // n <= 4    # the remainder is the loop's `range`

    A count is the same alone and beside five busy workers, and one more
    call on the path moves it by exactly `n`.  The collector is held off
    inside the block: its callbacks are not the path's."""

    def __init__(self):
        self.names: collections.Counter = collections.Counter()

    @property
    def total(self) -> int:
        return sum(self.names.values())

    def __call__(self, frame, event, arg):
        if event == "call":
            self.names[frame.f_code.co_qualname] += 1
        elif event == "c_call":
            self.names[getattr(arg, "__qualname__", repr(arg))] += 1

    def __enter__(self) -> "counted_calls":
        import gc

        self._gc_was = gc.isenabled()
        gc.disable()
        sys.setprofile(self)
        return self

    def __exit__(self, *exc) -> None:
        import gc

        sys.setprofile(None)
        # its own way out, seen before the profile came off
        self.names -= collections.Counter(
            {"counted_calls.__exit__": 1, "setprofile": 1})
        if self._gc_was:
            gc.enable()


def call_ceiling(production: int) -> int:
    """The ceiling for a hot path's `counted_calls`, given its count on the
    PRODUCTION build.  Under DYN_TPU_LOCKCHECK a lock is a TrackedLock
    whose order and hold bookkeeping is 31 calls of its own an acquire:
    ten times the count there, a sanity ceiling."""
    from .analysis import contracts

    return production if contracts.checks_mode() == "off" else 10 * production


def dispatches(engine) -> list:
    """What an engine has handed to the device, read off its ring's step
    slices in dispatch order (`seq`): one {"kind", "n_steps", "blocks",
    "pending", "t"} a dispatch, `kind` "prefill" | "fused" | "mixed" |
    "spec" | "decode" (a `prefill_chunk` with a fused decode chain is a
    "prefill" and a "fused"), `pending` the `rung_select` made inside the
    slice (whether prompts waited when the rung was chosen; False where
    none was), `t` the slice's start in seconds.  A slice is written when
    its step ENDS: a dispatch shows one step later than it was made."""
    events = engine.events.snapshot()
    rungs = [(t, a["pending"]) for t, _, k, a in events if k == "rung_select"]
    kinds = {"prefill_chunk": "prefill", "mixed_step": "mixed",
             "spec_round": "spec", "decode_block": "decode"}
    out = []
    for t, dur, kind, a in sorted(
            (e for e in events if e[2] in kinds), key=lambda e: e[3]["seq"]):
        pending = any(p for at, p in rungs if t <= at <= t + dur)
        out.append({"kind": kinds[kind], "n_steps": a.get("n_steps", 0),
                    "blocks": a.get("blocks", 1), "pending": pending,
                    "t": t / 1e9})
        if a.get("fused_blocks"):
            out[-1]["n_steps"] = 0
            out.append({"kind": "fused", "n_steps": a["n_steps"],
                        "blocks": a["fused_blocks"], "pending": False,
                        "t": t / 1e9})
    return out


@contextlib.asynccontextmanager
async def local_control_plane() -> AsyncIterator[ControlPlaneServer]:
    server = await ControlPlaneServer().start()
    try:
        yield server
    finally:
        await server.stop()


@contextlib.asynccontextmanager
async def threaded_control_plane() -> AsyncIterator[str]:
    """A ControlPlaneServer on its OWN thread + event loop, yielding its
    address. Use when test code blocks the main loop while talking to the
    control plane (e.g. admission-time G4 reads) — in production the
    server is a separate process, so the main loop can never starve it."""
    import asyncio as _a
    import threading

    started = threading.Event()
    holder = {}

    def run():
        loop = _a.new_event_loop()
        _a.set_event_loop(loop)
        server = loop.run_until_complete(ControlPlaneServer().start())
        holder["loop"], holder["server"] = loop, server
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, name="test-control-plane", daemon=True)
    t.start()
    started.wait(10)
    try:
        yield holder["server"].address
    finally:
        loop = holder["loop"]
        fut = _a.run_coroutine_threadsafe(holder["server"].stop(), loop)
        try:
            # bounded waits off the caller's loop: teardown must not
            # stall other coroutines sharing it
            await _a.to_thread(fut.result, 5)
        except Exception:  # lint: allow(swallowed-exception): best-effort test teardown; server may already be gone
            pass
        loop.call_soon_threadsafe(loop.stop)
        await _a.to_thread(t.join, 5)


@contextlib.asynccontextmanager
async def local_runtime() -> AsyncIterator[DistributedRuntime]:
    """One runtime with an embedded control plane."""
    rt = await DistributedRuntime.detached()
    try:
        yield rt
    finally:
        await rt.shutdown(graceful=False)


def tiny_tokenizer():
    """A real (trained) byte-level BPE tokenizer for tests — no downloads.

    Trained on a fixed corpus so ids are stable across runs.  Vocab is the
    260-symbol floor (256 byte alphabet + 4 specials); size the paired
    model's vocab from ``tok.vocab_size``, never a constant.
    """
    from tokenizers import Tokenizer, models, pre_tokenizers, decoders, trainers

    from .llm.tokenizer import HuggingFaceTokenizer

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=260,
        special_tokens=["<|endoftext|>", "<|user|>", "<|assistant|>", "<|system|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    corpus = [
        "the quick brown fox jumps over the lazy dog",
        "hello world, how are you today?",
        "paged attention on tpu with jax and pallas",
        "0123456789 !@#$%^&*()",
    ]
    tok.train_from_iterator(corpus, trainer)
    # appended AFTER training so every other id is unchanged; used by the
    # multimodal path as the single-image placeholder
    tok.add_special_tokens(["<image>"])
    eos = tok.token_to_id("<|endoftext|>")
    return HuggingFaceTokenizer(tok, eos_token_ids=[eos])


@contextlib.asynccontextmanager
async def local_cluster(n: int = 1):
    """A control plane + n runtimes (simulating n worker processes)."""
    server = await ControlPlaneServer().start()
    runtimes = []
    try:
        for _ in range(n):
            runtimes.append(await DistributedRuntime.connect(server.address))
        yield server, runtimes
    finally:
        for rt in runtimes:
            await rt.shutdown(graceful=False)
        await server.stop()


def export_vl_state_dict(model) -> dict:
    """Flatten an HF Qwen-VL-class state_dict into the PUBLISHED
    checkpoint layout (`visual.*` + `model.*` + `lm_head.weight`) as
    float32 numpy — shared by the verify drivers and the round-trip
    tests so they always write the same key mapping."""
    import numpy as np

    tensors = {}
    for k, v in model.state_dict().items():
        if k.startswith("model.visual."):
            k2 = k[len("model."):]
        elif k.startswith("model.language_model."):
            k2 = "model." + k[len("model.language_model."):]
        else:
            k2 = k
        tensors[k2] = np.ascontiguousarray(
            np.asarray(v.detach().to("cpu").numpy(), np.float32))
    return tensors
