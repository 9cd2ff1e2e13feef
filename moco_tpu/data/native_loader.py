"""ctypes binding for the native C++ staging loader (native/staging_loader.cc).

The reference leans on native code for its input path — PIL/libjpeg decode in
32 worker processes (`main_moco.py:≈L260-270`), or NVIDIA DALI in the bl0
fork (SURVEY §2.10). This is the TPU-native equivalent: a C++ thread pool in
the single controller process that turns JPEG files into fixed-size uint8
staging canvases (decode → transpose-if-portrait → bilinear fit-resize of
the WHOLE image + edge-replicated padding, with a per-image
`(valid_h, valid_w, rot)` extent); the randomized augmentation then runs ON
DEVICE (data/augment.py) over the true image area.

The shared library is compiled on first use (g++ + libjpeg, both in the
image) from THIS checkout's source: the file name carries the source's
hash, because in a copied tree an mtime says nothing about which source a
binary came from. A failed build raises with the compiler's output;
`ImageFolder(backend="auto")` then says so and decodes with PIL.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from moco_tpu.utils.logging import log_event

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native"))
_build_lock = threading.Lock()


def _ensure_built() -> str:
    """Path of the library built from the current `staging_loader.cc`,
    compiling it if this source has not been built here yet."""
    with open(os.path.join(_NATIVE_DIR, "staging_loader.cc"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    lib = os.path.join(_NATIVE_DIR, f"libstaging_loader-{digest}.so")
    with _build_lock:
        if not os.path.exists(lib):
            # per-pid temp + atomic rename: concurrent first users
            # (staging-server workers) never load a half-written file
            tmp = f"{lib}.{os.getpid()}.tmp"
            proc = subprocess.run(
                ["make", "-C", _NATIVE_DIR, f"OUT={os.path.basename(tmp)}"],
                capture_output=True, text=True,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    "native staging loader build failed "
                    f"(make rc={proc.returncode}):\n{proc.stderr.strip()}")
            os.replace(tmp, lib)
    return lib


class NativeStagingLoader:
    """Threaded JPEG→staging-canvas batch loader. Raises RuntimeError if the
    native library cannot be built."""

    def __init__(self, stage_h: int, stage_w: int, num_threads: int | None = None):
        self._lib = ctypes.CDLL(_ensure_built())
        self._lib.sl_create.restype = ctypes.c_void_p
        self._lib.sl_create.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
        self._lib.sl_load_batch.restype = ctypes.c_int
        self._lib.sl_load_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
        ]
        self._lib.sl_destroy.argtypes = [ctypes.c_void_p]
        self._lib.sl_version.restype = ctypes.c_int
        self.version = int(self._lib.sl_version())
        if num_threads is None:
            num_threads = max(os.cpu_count() or 1, 1)
        self.num_threads = num_threads
        self.stage_h = stage_h
        self.stage_w = stage_w
        # cumulative decode telemetry: a zero-canvas batch poisoning training
        # must be VISIBLE (metered by the driver, ISSUE 1 satellite), not a
        # discarded return value. Locked: staging workers (ISSUE 3) call
        # load_batch concurrently for disjoint sub-slices of one batch.
        self.total_images = 0
        self.total_failures = 0
        self._meter_lock = threading.Lock()
        self._handle = self._lib.sl_create(num_threads, stage_h, stage_w)
        if not self._handle:
            raise RuntimeError("sl_create failed")

    def load_batch(
        self,
        paths: list[str],
        out: np.ndarray | None = None,
        extents: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Decode `paths` in parallel →
        (`[n, H, W, 3] uint8`, `[n, 3] int32 (h, w, rot)`, n_failures).
        Failed images come back as zero canvases with full-canvas extent.

        `out`/`extents` let the caller own the destination (ISSUE 3: staging
        workers hand in disjoint row ranges of a shared pooled canvas, so the
        decode writes land in place with no per-image Python round-trips and
        no assembly copy). They must be C-contiguous with the exact shapes
        below; omitted, fresh arrays are allocated."""
        n = len(paths)
        if out is None:
            out = np.empty((n, self.stage_h, self.stage_w, 3), dtype=np.uint8)
        if extents is None:
            extents = np.empty((n, 3), dtype=np.int32)
        if out.shape != (n, self.stage_h, self.stage_w, 3) or out.dtype != np.uint8:
            raise ValueError(
                f"out must be uint8 [{n}, {self.stage_h}, {self.stage_w}, 3], "
                f"got {out.dtype} {out.shape}"
            )
        if extents.shape != (n, 3) or extents.dtype != np.int32:
            raise ValueError(f"extents must be int32 [{n}, 3], got "
                             f"{extents.dtype} {extents.shape}")
        if not out.flags.c_contiguous or not extents.flags.c_contiguous:
            raise ValueError("out/extents must be C-contiguous")
        arr = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
        failures = self._lib.sl_load_batch(
            self._handle,
            arr,
            n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            extents.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        failures = int(failures)
        with self._meter_lock:
            self.total_images += n
            if failures:
                self.total_failures += failures
        if failures:
            log_event(
                "data",
                f"native decode: {failures}/{n} failure(s) in batch "
                f"(cumulative {self.total_failures}/{self.total_images})",
            )
        return out, extents, failures

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.sl_destroy(handle)
            self._handle = None
