"""Where JAX's persistent compilation cache lives, and the program store
beside it.

One rule for every entry point that builds an engine (worker, run, bench,
the profiler sweep): when `JAX_COMPILATION_CACHE_DIR` is set the
environment owns the location and nothing here touches it; otherwise the
cache sits at one fixed, git-ignored path inside the checkout.  The path is
part of the cache key, so it is never a temp name, a pid or a timestamp —
a directory that moves never hits.

The persistent cache keeps compiled executables, keyed by the lowered
module: a warm start still traced and lowered every program in Python only
to compute that key.  `ProgramStore` (`<dir>/programs/`) keeps the lowered
modules themselves (`jax.export`), keyed WITHOUT tracing: by what a program
is called with and everything its body closes over
(`analysis/xla_ledger.py` `ledgered_jit` asks it on every jit cache miss of
a program `engine/layout.py` `Layout.wrap` describes).  Deleting the
directory is always safe: a miss derives the module and writes it again.
"""

from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import tempfile
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

_PACKAGE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_DIR = os.path.join(os.path.dirname(_PACKAGE), ".jax_cache")


def configure() -> str:
    """Turn the persistent cache and the program store on and return their
    directory.  Call before the first compilation."""
    import jax

    from .analysis import xla_ledger

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, not only those that took over a second to
    # compile: a restarted worker should compile nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    xla_ledger.set_program_store(ProgramStore(os.path.join(path, "programs")))
    return path


def source_digest() -> str:
    """One digest over the bytes of every `.py` under the package: a body
    is Python, so any edit anywhere under it may change what a program
    lowers to, and every stored module of other sources misses."""
    h = hashlib.blake2b(digest_size=16)
    for base, dirs, files in os.walk(_PACKAGE):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(base, name)
                h.update(os.path.relpath(path, _PACKAGE).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


# file = blake2b-32 of the rest | 4-byte length of the notes | notes (JSON)
# | the serialised `jax.export.Exported`
_DIGEST = 32


class ProgramStore:
    """Lowered step programs on disk, one file a key."""

    def __init__(self, root: str):
        self.root = root

    @functools.cached_property
    def environment(self) -> Dict[str, str]:
        """What a lowered module depends on besides the program: the
        versions that lowered it, the device it was lowered for, the
        sources.  Read at the first key (the backend is up by then)."""
        import jax
        import jaxlib

        dev = jax.devices()[0]
        return {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": dev.platform,
            "platform_version": dev.client.platform_version,
            "device_kind": dev.device_kind,
            "x64": str(jax.config.jax_enable_x64),
            "matmul_precision": str(jax.config.jax_default_matmul_precision),
            "prng": str(jax.config.jax_default_prng_impl),
            "sources": source_digest(),
        }

    def key(self, **program: Any) -> str:
        """The file name of a program: a digest over its description (by
        `repr`: names, tuples, frozen dataclasses) and the environment."""
        text = json.dumps(
            {"program": {k: repr(v) for k, v in program.items()},
             "environment": self.environment}, sort_keys=True)
        return hashlib.blake2b(text.encode(), digest_size=20).hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key + ".jaxprog")

    def load(self, key: str) -> Optional[Tuple[Any, List[list]]]:
        """(the `Exported`, the trace-time notes written with it), or None:
        a file that is absent, truncated, unreadable or of a serialisation
        this jax refuses is a miss, and the writer replaces it."""
        from jax import export

        try:
            with open(self._path(key), "rb") as f:
                raw = f.read()
        except OSError:
            return None
        body = raw[_DIGEST:]
        if (len(raw) < _DIGEST + 4
                or hashlib.blake2b(body, digest_size=_DIGEST).digest()
                != raw[:_DIGEST]):
            logger.warning("program store: %s is damaged, rewriting", key)
            return None
        n = int.from_bytes(body[:4], "big")
        try:
            notes = json.loads(body[4:4 + n])
            return export.deserialize(bytearray(body[4 + n:])), notes
        except Exception as e:  # noqa: BLE001 — any refusal is a miss
            logger.warning("program store: %s does not load (%s: %s), "
                           "rewriting", key, type(e).__name__, e)
            return None

    def save(self, key: str, exported: Any, notes: List[list]) -> bool:
        """Write under a temporary name and rename: readers, and the other
        ranks of a host writing the same key, see a whole file or none.
        False (logged) where the directory cannot be written."""
        head = json.dumps(notes).encode()
        body = len(head).to_bytes(4, "big") + head + bytes(exported.serialize())
        try:
            os.makedirs(self.root, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(hashlib.blake2b(
                        body, digest_size=_DIGEST).digest() + body)
                os.replace(tmp, self._path(key))
            except BaseException:
                os.unlink(tmp)
                raise
        except OSError as e:
            logger.warning("program store: cannot write %s: %s", key, e)
            return False
        return True
