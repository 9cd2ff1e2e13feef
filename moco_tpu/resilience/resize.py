"""Elastic training: checkpoint–resize–relaunch across changing hardware
(ISSUE 11 tentpole).

A pod resize used to mean a manual restart even though every piece needed
to survive it already existed separately: the checkpoint dialect shim
rebuilds gradsync accumulators across mesh-size changes
(`checkpoint.TRAIN_STATE_DIALECTS`), the run supervisor classifies deaths
and relaunches within a budget (`resilience/supervisor.py`), and the
position sidecars preserve the data window. This module is the wiring
that turns those pieces into ONE automatic loop:

  - `ResizeListener` (child side, wired by the train driver): a
    `<telemetry_dir>/resize.request` trigger file (polled time-gated at
    step boundaries, the `trace.trigger` pattern) or a SIGUSR2 flips a
    flag; the driver finishes the in-flight step, writes a clean elastic
    checkpoint, and exits `EXIT_RESIZE` (49) — the "relaunch me onto a
    different mesh" exit, distinct from a preemption's 43.
  - `ResizeController` (supervisor side): accepts resize requests (the
    same trigger file, or a SIGUSR2 delivered to the SUPERVISOR), signals
    the child, and on the child's 49 rewrites the relaunch argv — the new
    device count (argparse last-wins append), an optional
    `--grad-sync-cadence` override when the new mesh is flagged
    slow-linked. `--resume auto` + the dialect shim then restore
    the state onto the new mesh with fresh-zero gradsync accumulators.
  - `read_recorded_devices` / `argv_device_count`: the relaunch-preflight
    membership check — every checkpoint's position sidecar records the
    mesh size it was saved under, so a supervisor about to relaunch onto
    a different device count can log the `mesh_change` incident BEFORE
    the restore shim discovers it.

Request file format: `key=value` pairs, whitespace- or comma-separated,
e.g. `devices=2 grad_sync_cadence=4` or just an empty file ("resize to
whatever is visible now"). `slow=1` flags the new mesh as slow-linked
without naming a cadence — the supervisor then applies its configured
`--resize-slow-cadence`. Consumption renames the file to
`resize.request.honored` (atomic), so a stale request can never re-fire a
resize into the next incarnation.

Everything here is PURE stdlib — the supervisor imports it, and the
supervisor's contract is surviving the failures that kill the jax
runtime (mocolint R11 pins the import discipline).
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import threading
import time

from moco_tpu.utils.logging import log_event

RESIZE_REQUEST_FILENAME = "resize.request"
HONORED_SUFFIX = ".honored"

# argv spellings that pin a device count, in either `--flag N` or
# `--flag=N` form. `--fake-devices` is the CPU-proxy spelling (forces N
# fake XLA CPU devices — the 1→2→1 drill), `--num-devices` caps the real
# visible device set.
DEVICE_FLAGS = ("--num-devices", "--fake-devices")


@dataclasses.dataclass
class ResizeRequest:
    """One parsed resize request. `devices=None` means "resize to whatever
    the relaunch sees" (the membership-change case — the argv keeps its
    device flags and the new hardware defines the mesh)."""

    devices: int | None = None
    grad_sync_cadence: int | None = None
    sharding: str | None = None  # ISSUE 15: switch the sharding mode on
                                 # relaunch (dp/fsdp/fsdp_tp) — e.g. a
                                 # grow onto a pod flips dp→fsdp in the
                                 # same resize; the dialect-3 restore +
                                 # sidecar stamp make the mode hop safe
    slow: bool = False           # new mesh flagged slow-linked: the
                                 # supervisor applies its configured
                                 # cadence override
    source: str = "request"      # "request" | "sigusr2" | "chaos" |
                                 # "mesh_change"


def parse_resize_request(text: str, source: str = "request") -> ResizeRequest:
    """`"devices=2 grad_sync_cadence=4"` → ResizeRequest. Empty text is a
    valid request (resize to the visible device count). Unknown keys are
    rejected loudly — a typo'd `device=2` silently resizing to the old
    count would be worse than the crash."""
    req = ResizeRequest(source=source)
    for part in text.replace(",", " ").split():
        key, sep, value = part.partition("=")
        key = key.strip()
        if not sep:
            raise ValueError(f"malformed resize request entry {part!r} "
                             "(expected key=value)")
        if key == "devices":
            req.devices = int(value)
            if req.devices < 1:
                raise ValueError(f"resize devices must be >= 1, got {value}")
        elif key == "grad_sync_cadence":
            req.grad_sync_cadence = int(value)
            if req.grad_sync_cadence < 1:
                raise ValueError(
                    f"resize grad_sync_cadence must be >= 1, got {value}")
        elif key == "sharding":
            if value not in ("dp", "fsdp", "fsdp_tp"):
                raise ValueError(
                    f"resize sharding must be dp/fsdp/fsdp_tp, got {value!r}")
            req.sharding = value
        elif key == "slow":
            req.slow = bool(int(value))
        else:
            raise ValueError(
                f"unknown resize request key {key!r}; known: devices, "
                "grad_sync_cadence, sharding, slow"
            )
    return req


def request_path(telemetry_dir: str) -> str:
    return os.path.join(telemetry_dir, RESIZE_REQUEST_FILENAME)


def write_resize_request(
    telemetry_dir: str,
    devices: int | None = None,
    grad_sync_cadence: int | None = None,
    slow: bool = False,
    sharding: str | None = None,
) -> str:
    """Drop a resize request next to trace.trigger (atomic: a supervisor
    polling mid-write must never parse half a request). Returns the path."""
    parts = []
    if devices is not None:
        parts.append(f"devices={int(devices)}")
    if grad_sync_cadence is not None:
        parts.append(f"grad_sync_cadence={int(grad_sync_cadence)}")
    if sharding is not None:
        parts.append(f"sharding={sharding}")
    if slow:
        parts.append("slow=1")
    os.makedirs(telemetry_dir, exist_ok=True)
    path = request_path(telemetry_dir)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(" ".join(parts) + "\n")
    os.replace(tmp, path)
    return path


def consume_resize_request(telemetry_dir: str,
                           source: str = "request") -> ResizeRequest | None:
    """Atomically claim a pending request (rename to `.honored` — exactly
    one of N racing consumers wins, and a relaunched child can never
    re-trigger on a stale file). None when no request is pending or it is
    unparseable (logged, never fatal: a malformed operator request must
    not take the run down)."""
    path = request_path(telemetry_dir)
    honored = path + HONORED_SUFFIX
    try:
        os.replace(path, honored)  # atomic claim; overwrites the last one
    except OSError:
        return None  # no pending request
    return read_honored_request(telemetry_dir, source=source)


def read_honored_request(telemetry_dir: str,
                         source: str = "request") -> ResizeRequest | None:
    """The last CLAIMED request's payload. The supervisor falls back to
    this when the child's own file poll won the consume race (the claim
    is a rename, so the payload — the target device count — survives it);
    `ResizeController.apply` deletes the file once honored so a stale
    payload can never leak into a later, payload-less resize."""
    honored = request_path(telemetry_dir) + HONORED_SUFFIX
    try:
        with open(honored, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return None
    try:
        return parse_resize_request(text, source=source)
    except ValueError as e:
        log_event("resize", f"ignoring unparseable resize request: {e}")
        return None


# -- membership bookkeeping ---------------------------------------------------


def read_recorded_devices(ckpt_dir: str) -> tuple[int, int] | None:
    """`(step, devices)` of the NEWEST checkpoint step whose position
    sidecar records the mesh size it was saved under (checkpoint.
    write_position stamps `devices` on every save). None when no step
    records one — pre-elastic checkpoints stay silent, never guessed at.
    Stdlib-only: the jax-free supervisor runs this at relaunch preflight."""
    from moco_tpu.resilience.integrity import position_path

    try:
        names = os.listdir(ckpt_dir)
    except OSError:
        return None
    for name in sorted((n for n in names if n.isdigit()), key=int,
                       reverse=True):
        try:
            with open(position_path(ckpt_dir, int(name)),
                      encoding="utf-8") as f:
                payload = json.load(f)
            devices = int(payload["devices"])
        except (OSError, ValueError, KeyError, TypeError,
                json.JSONDecodeError):
            continue
        return int(name), devices
    return None


def argv_device_count(argv: list[str]) -> int | None:
    """The device count the argv pins (`--num-devices N` /
    `--fake-devices N`, either flag form; LAST occurrence wins — the same
    argparse semantics the resize append relies on). None when the argv
    leaves the mesh to the visible hardware."""
    found: int | None = None
    i = 0
    while i < len(argv):
        arg = argv[i]
        for flag in DEVICE_FLAGS:
            value = None
            if arg == flag and i + 1 < len(argv):
                value = argv[i + 1]
            elif arg.startswith(flag + "="):
                value = arg[len(flag) + 1:]
            if value is not None:
                try:
                    n = int(value)
                except ValueError:
                    continue
                if n > 0:  # --fake-devices 0 means "off", not a count
                    found = n
        i += 1
    return found


def pick_device_flag(argv: list[str], default: str = "--num-devices") -> str:
    """The flag the resize append should use: whichever device flag the
    argv already speaks (a `--fake-devices` CPU drill must be resized in
    its own dialect), else `default`."""
    for arg in argv:
        for flag in DEVICE_FLAGS:
            if arg == flag or arg.startswith(flag + "="):
                return flag
    return default


# -- child side ---------------------------------------------------------------


class ResizeListener:
    """Converts a resize request into a poll-able flag inside the train
    driver (the `PreemptionHandler` pattern): SIGUSR2 sets it immediately;
    `poll()` additionally checks the trigger file time-gated (`poll_secs`),
    consuming it on trigger so an unsupervised relaunch can never re-fire
    on the stale file. The driver finishes the in-flight step, writes the
    elastic checkpoint, and exits `EXIT_RESIZE`.

    Signal handlers install from the main thread only (pytest workers and
    nested drivers get a file-poll-only listener, no special-casing)."""

    def __init__(self, telemetry_dir: str = "", poll_secs: float = 0.5):
        self.telemetry_dir = telemetry_dir
        self.poll_secs = float(poll_secs)
        self._flag = threading.Event()
        self._last_poll = float("-inf")
        self._prev = None
        self._installed = False

    def _handle(self, signum, frame):
        if not self._flag.is_set():
            log_event(
                "resize",
                "caught SIGUSR2: finishing the in-flight step, then writing "
                "an elastic checkpoint and exiting for the resize relaunch",
            )
        self._flag.set()

    def __enter__(self) -> "ResizeListener":
        if threading.current_thread() is threading.main_thread():
            self._prev = signal.signal(signal.SIGUSR2, self._handle)
            self._installed = True
        return self

    def __exit__(self, *exc) -> bool:
        if self._installed:
            if self._flag.is_set():
                # the resize is being HONORED: the listener exits (the
                # driver's ExitStack closes) BEFORE the elastic checkpoint
                # is written, and the supervisor may still deliver its
                # SIGUSR2 in that window — restoring the default
                # disposition would let a late duplicate signal TERMINATE
                # the child mid-save. Leave SIGUSR2 ignored for the rest
                # of this (already-exiting) process.
                signal.signal(signal.SIGUSR2, signal.SIG_IGN)
            else:
                signal.signal(signal.SIGUSR2, self._prev)
            self._installed = False
        return False

    @property
    def triggered(self) -> bool:
        return self._flag.is_set()

    def trigger(self, source: str = "chaos") -> None:
        """Programmatic trigger (the chaos `resize_at_step` drill)."""
        if not self._flag.is_set():
            log_event("resize", f"resize triggered ({source}): exiting for "
                                "relaunch after the elastic checkpoint")
        self._flag.set()

    def poll(self, now: float | None = None) -> bool:
        """Current flag state, refreshed from the trigger file at most once
        per `poll_secs` (one `os.replace` attempt — the fast path is a
        monotonic-clock compare). Supervised runs normally never reach the
        file: the supervisor consumes it first and SIGUSR2s us."""
        if self._flag.is_set():
            return True
        if not self.telemetry_dir:
            return False
        now = time.monotonic() if now is None else now
        if now - self._last_poll < self.poll_secs:
            return False
        self._last_poll = now
        req = consume_resize_request(self.telemetry_dir)
        if req is not None:
            self.trigger(source="trigger file")
        return self._flag.is_set()


# -- supervisor side ----------------------------------------------------------


class ResizeController:
    """The supervisor's half of the elastic loop. Owns the armed request
    state and the relaunch-argv rewrite; the `Supervisor` calls:

      - `poll()` each monitor cycle — arms from the trigger file (or a
        SIGUSR2 the CLI routed to `signal_resize`), returns the request
        once so the supervisor can signal the child and emit the
        `resize_request` incident;
      - `take()` after a child exits `EXIT_RESIZE` — the armed request,
        else a last-chance file claim (the chaos drill's child writes the
        file and exits faster than the poll cadence), else an empty
        request (resize to whatever the hardware shows);
      - `apply(req, argv)` before the relaunch — mutates argv in
        place: device-count append (argparse last-wins) and the cadence
        override.
    """

    def __init__(self, telemetry_dir: str, *,
                 device_flag: str = "",
                 slow_cadence: int = 0,
                 poll_gate_secs: float = 0.5):
        self.telemetry_dir = telemetry_dir
        self.device_flag = device_flag  # "" = pick from the argv itself
        self.slow_cadence = int(slow_cadence)
        self.poll_gate_secs = float(poll_gate_secs)
        self.armed: ResizeRequest | None = None
        self.armed_at_wall: float = 0.0
        self.resizes_applied = 0
        self._signal_flag = threading.Event()
        self._last_poll = float("-inf")

    def signal_resize(self) -> None:
        """SIGUSR2-to-the-supervisor entry point (tools/supervise.py
        installs it): arm a resize using the trigger file's payload when
        one is pending, else an empty request. Signal-handler-safe: just
        an Event set; the monitor loop's next poll does the file I/O."""
        self._signal_flag.set()

    def poll(self, now: float | None = None) -> ResizeRequest | None:
        """Newly-armed request, exactly once per arming; None otherwise."""
        if self.armed is not None:
            return None  # already armed: waiting for the child to exit
        via_signal = self._signal_flag.is_set()
        now = time.monotonic() if now is None else now
        if not via_signal and now - self._last_poll < self.poll_gate_secs:
            return None
        self._last_poll = now
        req = consume_resize_request(self.telemetry_dir)
        if via_signal:
            self._signal_flag.clear()
            if req is None:
                # the CHILD's listener may have won the file-claim race
                # between the operator's write and this SIGUSR2: the
                # payload (the target device count) survives at the
                # honored path — dropping it would resize to "visible"
                # instead of what the operator asked for
                req = read_honored_request(self.telemetry_dir)
            if req is None:
                req = ResizeRequest(source="sigusr2")
            else:
                req.source = "sigusr2"
        if req is not None:
            self.armed = req
            self.armed_at_wall = time.time()
        return req

    def take(self) -> ResizeRequest:
        """Claim the request a just-exited `EXIT_RESIZE` child honored:
        the armed one, else an unconsumed file (the chaos drill's child
        writes it and exits faster than the poll cadence), else the
        honored file the CHILD's own poll claimed, else an empty request
        (resize to whatever the hardware shows)."""
        req = (self.armed
               or consume_resize_request(self.telemetry_dir)
               or read_honored_request(self.telemetry_dir, source="exit"))
        if req is None:
            req = ResizeRequest(source="exit")
        if not self.armed_at_wall:
            self.armed_at_wall = time.time()
        self.armed = None
        return req

    def cadence_override(self, req: ResizeRequest) -> int | None:
        """The `--grad-sync-cadence` the relaunch should carry: an explicit
        request value wins; a `slow=1` flag applies the supervisor's
        configured slow-link cadence; neither means no override."""
        if req.grad_sync_cadence is not None:
            return req.grad_sync_cadence
        if req.slow and self.slow_cadence > 0:
            return self.slow_cadence
        return None

    def apply(self, req: ResizeRequest, argv: list[str]) -> dict:
        """Rewrite the relaunch argv IN PLACE for the resize; returns a
        summary dict for the `resize_relaunch` incident record.

        Appends (argparse last-wins) rather than edits: the original
        operator argv stays visible in the launch record, and repeated
        resizes stack correctly."""
        old_devices = argv_device_count(argv)
        summary: dict = {"source": req.source, "devices_from": old_devices}
        if req.devices is not None:
            flag = self.device_flag or pick_device_flag(argv)
            argv += [flag, str(int(req.devices))]
            summary["devices_to"] = int(req.devices)
            summary["device_flag"] = flag
        else:
            summary["devices_to"] = None  # whatever the hardware shows
        cadence = self.cadence_override(req)
        if cadence is not None:
            argv += ["--grad-sync-cadence", str(int(cadence))]
            summary["grad_sync_cadence"] = int(cadence)
        if req.sharding is not None:
            # ISSUE 15: the sharding mode rides the same last-wins append —
            # an argv that already says --sharding fsdp keeps saying it on
            # a mode-less resize (nothing appended), and a mode-carrying
            # request flips it for the relaunch
            argv += ["--sharding", req.sharding]
            summary["sharding"] = req.sharding
        try:
            # honored payload applied: a stale copy must not leak into a
            # later payload-less resize's take() fallback
            os.remove(request_path(self.telemetry_dir) + HONORED_SUFFIX)
        except OSError:
            pass
        self.resizes_applied += 1
        self.armed_at_wall = 0.0
        return summary
