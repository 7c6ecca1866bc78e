"""Atomic, CRC-checked JSON store for autotuned kernel configs.

Written through a tmp file + ``os.replace``, so concurrent writers and
crashes can never leave a torn file behind, with two hardenings:

* every payload carries a CRC32 of its canonical entries blob — a
  truncated or bit-rotted cache file is *detected* and treated as empty
  (with a runlog ``alert`` and a ``tune.store.corrupt_total`` counter)
  instead of either crashing the process or silently feeding garbage
  block configs to the kernels;
* every entry carries the *kernel fingerprint* it was measured against —
  a hash of the kernel source + config schema — so entries go stale
  automatically when the kernel implementation changes, rather than
  pinning yesterday's tiling onto today's kernel.

A bad tune cache must never take the process down: the worst case is
always "fall back to the built-in defaults".
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import zlib
from typing import Dict, Optional, Tuple

from paddle_tpu.core import locks
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import enforce
from paddle_tpu.observability import runlog

__all__ = ["TuneStore", "TuneKey", "kernel_fingerprint", "STORE_VERSION"]

STORE_VERSION = 1


def kernel_fingerprint(*parts: str) -> str:
    """Stable hash over kernel source text + config-schema strings. Any
    edit to a hashed part yields a new fingerprint, invalidating every
    store entry recorded under the old one."""
    h = hashlib.sha256()
    for p in parts:
        h.update(p.encode("utf-8"))
        h.update(b"\x00")
    return h.hexdigest()[:16]


class TuneKey:
    """Composite key ``kernel|shape_bucket|dtype|variant|device_kind`` —
    the dimensions a tiling decision actually depends on."""

    SEP = "|"

    @classmethod
    def render(cls, kernel: str, shape_bucket: str = "-", dtype: str = "-",
               variant: str = "-", device_kind: str = "-") -> str:
        for part in (kernel, shape_bucket, dtype, variant, device_kind):
            enforce(cls.SEP not in str(part),
                    f"tune key part may not contain {cls.SEP!r}: {part!r}")
        return cls.SEP.join((kernel, shape_bucket, dtype, variant, device_kind))

    @classmethod
    def parse(cls, rendered: str) -> Tuple[str, str, str, str, str]:
        parts = rendered.split(cls.SEP)
        enforce(len(parts) == 5, f"malformed tune key {rendered!r}")
        return tuple(parts)  # type: ignore[return-value]


def _entries_crc(entries: dict) -> int:
    blob = json.dumps(entries, sort_keys=True, separators=(",", ":"))
    return zlib.crc32(blob.encode("utf-8")) & 0xFFFFFFFF


class TuneStore:
    """Disk-backed map of rendered :class:`TuneKey` -> winner config dict.

    Each entry: ``{"fingerprint": str, "config": {...}, "ms": float,
    "candidates": int}``. ``path=None`` keeps the store in-memory.
    Corrupt/truncated files load as empty (alerted, counted, never
    raised); saves are atomic."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._lock = locks.Lock("tune.store")
        self._entries: Dict[str, dict] = {}
        self.corrupt = False  # last load found a bad file
        if path and os.path.exists(path):
            self.load()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self):
        with self._lock:
            return list(self._entries.keys())

    def get(self, rendered_key: str,
            fingerprint: Optional[str] = None) -> Optional[dict]:
        """Entry for ``rendered_key`` — or None when absent or recorded
        under a different kernel fingerprint (stale)."""
        with self._lock:
            ent = self._entries.get(rendered_key)
        if ent is None:
            return None
        if fingerprint is not None and ent.get("fingerprint") != fingerprint:
            return None
        return dict(ent)

    def is_stale(self, rendered_key: str, fingerprint: str) -> bool:
        """True when an entry exists but was measured against a different
        kernel (fingerprint mismatch)."""
        with self._lock:
            ent = self._entries.get(rendered_key)
        return ent is not None and ent.get("fingerprint") != fingerprint

    def put(self, rendered_key: str, fingerprint: str, config: dict,
            ms: Optional[float] = None, candidates: int = 0) -> None:
        ent = {"fingerprint": fingerprint, "config": dict(config),
               "candidates": int(candidates)}
        if ms is not None:
            ent["ms"] = round(float(ms), 6)
        with self._lock:
            self._entries[rendered_key] = ent

    def prune_stale(self, kernel: str, fingerprint: str) -> int:
        """Drop every entry for ``kernel`` whose fingerprint != current.
        Returns the number removed (an autotune run calls this so the
        file doesn't accrete dead generations)."""
        dropped = 0
        with self._lock:
            for rk in list(self._entries):
                if (rk.split(TuneKey.SEP, 1)[0] == kernel
                        and self._entries[rk].get("fingerprint") != fingerprint):
                    del self._entries[rk]
                    dropped += 1
        return dropped

    # -- persistence -------------------------------------------------------

    def save(self, path: Optional[str] = None) -> str:
        """Atomic write (tmp + ``os.replace``) with an entries CRC."""
        path = path or self.path
        enforce(path, "TuneStore.save needs a path")
        with self._lock:
            entries = {k: dict(v) for k, v in self._entries.items()}
        payload = {
            "version": STORE_VERSION,
            "crc": _entries_crc(entries),
            "entries": entries,
        }
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        # pid + thread ident: concurrent saves from threads of one process
        # must not share a tmp file (the loser's os.replace would ENOENT)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path

    def load(self, path: Optional[str] = None) -> None:
        """Tolerant load: any defect (unreadable, bad JSON, bad schema,
        CRC mismatch, future version) resets to empty and alerts — a
        corrupt tune cache degrades to defaults, never to a crash."""
        path = path or self.path
        enforce(path, "TuneStore.load needs a path")
        try:
            with open(path) as f:
                payload = json.load(f)
            enforce(isinstance(payload, dict) and "entries" in payload,
                    "malformed tune store")
            enforce(payload.get("version", 0) <= STORE_VERSION,
                    "tune store from a newer build")
            entries = payload["entries"]
            enforce(isinstance(entries, dict), "malformed tune entries")
            enforce(_entries_crc(entries) == payload.get("crc"),
                    "tune store CRC mismatch")
            for ent in entries.values():
                enforce(isinstance(ent, dict) and "config" in ent,
                        "malformed tune entry")
        except Exception as e:
            prof.inc_counter("tune.store.corrupt_total")
            runlog.emit("alert", source="tune.store", path=str(path),
                        error=str(e)[:200],
                        action="ignoring corrupt tune cache; using defaults")
            with self._lock:
                self._entries = {}
            self.corrupt = True
            return
        with self._lock:
            self._entries = {k: dict(v) for k, v in entries.items()}
        self.corrupt = False
