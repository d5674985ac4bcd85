"""Known-noise XLA stderr filtering for captured log tails.

A harness that runs __graft_entry__.py captures the last
few KB of stderr into BENCH_*/MULTICHIP_*.json ``tail`` fields. On
every CPU(-fallback) start, XLA's cpu_aot_loader logs a multi-KB
single-line machine-feature WARNING (see MULTICHIP_r05.json) that
drowns every useful line in that window. ``TF_CPP_MIN_LOG_LEVEL=2``
suppresses most of it, but the AOT loader line is emitted through a
path that ignores the knob on some jaxlib builds — so the entry
points additionally route fd 2 through :func:`install_fd_filter`,
which drops known-noise lines AT THE PIPE, before anything a harness
could capture. Everything else (including real XLA errors) passes
through byte-for-byte.

:func:`filter_tail` is the pure-string twin for consumers that
already hold a captured tail: drop the noise lines and keep the last
~10 meaningful ones.
"""

from __future__ import annotations

import atexit
import os
import threading

# substrings marking a stderr line as known noise. Matched per line —
# the cpu_aot_loader warning is ONE multi-KB line, so a single match
# drops the whole blob.
NOISE_MARKERS = (
    "cpu_aot_loader",
    "Loading XLA:CPU AOT result",
    "machine type for execution",
    "Machine type used for XLA:CPU compilation",
    "This could lead to execution errors such as SIGILL",
    # absl/TF banner noise that survives TF_CPP_MIN_LOG_LEVEL on
    # some builds
    "TensorFlow binary is optimized",
    "computation placer already registered",
)


def is_noise_line(line: str) -> bool:
    return any(m in line for m in NOISE_MARKERS)


def filter_tail(text: str, keep: int = 10) -> str:
    """Drop known-noise lines from a captured stderr tail and keep
    the last `keep` meaningful (non-empty, non-noise) lines."""
    lines = [ln for ln in text.splitlines()
             if ln.strip() and not is_noise_line(ln)]
    return "\n".join(lines[-keep:])


class _FdFilter:
    """Routes an OS-level fd (default 2) through a pipe; a daemon
    thread forwards every line that is not known noise to the
    original fd. Line-based: a line is held until its newline
    arrives, so the multi-KB one-line XLA warning is dropped whole.
    An unterminated trailing chunk is flushed on close/exit AND after
    a short idle window — a hard crash (C++ abort, SIGILL) never runs
    atexit, so holding a partial line indefinitely would lose exactly
    the diagnostic that mattered; the idle flush bounds that loss to
    whatever arrived in the final IDLE_FLUSH_S. (Bytes a crash leaves
    unread in the kernel pipe are inherently unrecoverable from
    inside the process — the filter trades that sliver for clean
    captured tails on every surviving path.)"""

    IDLE_FLUSH_S = 0.2

    def __init__(self, fd: int = 2):
        self.fd = fd
        self.saved = os.dup(fd)
        self._rd, self._wr = os.pipe()
        os.dup2(self._wr, fd)
        os.close(self._wr)
        self._thread = threading.Thread(target=self._pump,
                                        daemon=True)
        self._thread.start()
        atexit.register(self.close)

    def _pump(self) -> None:
        import select

        buf = b""
        try:
            while True:
                ready, _, _ = select.select([self._rd], [], [],
                                            self.IDLE_FLUSH_S)
                if not ready:
                    if buf:
                        # idle: forward the partial line now rather
                        # than risk dying with it (a leaked noise
                        # FRAGMENT beats a lost crash diagnostic)
                        self._emit(buf)
                        buf = b""
                    continue
                chunk = os.read(self._rd, 65536)
                if not chunk:
                    break
                buf += chunk
                while True:
                    nl = buf.find(b"\n")
                    if nl < 0:
                        break
                    line, buf = buf[:nl + 1], buf[nl + 1:]
                    self._emit(line)
        except OSError:
            pass
        if buf:
            self._emit(buf)

    def _emit(self, line: bytes) -> None:
        try:
            text = line.decode("utf-8", "replace")
        except Exception:       # noqa: BLE001 — never lose output
            text = ""
        if text and is_noise_line(text):
            return
        try:
            os.write(self.saved, line)
        except OSError:
            pass

    def close(self) -> None:
        """Restore the original fd and drain the pipe. Idempotent."""
        if self.saved is None:
            return
        try:
            os.dup2(self.saved, self.fd)
        except OSError:
            pass
        # closing the last write end EOFs the reader thread
        self._thread.join(timeout=2.0)
        for f in (self._rd, self.saved):
            try:
                os.close(f)
            except OSError:
                pass
        self.saved = None


_installed: _FdFilter | None = None


def install_fd_filter(fd: int = 2):
    """Install the stderr noise filter once per process (no-op on
    repeat calls, and disabled entirely by
    SHADOW_TPU_STDERR_FILTER=0). Returns the filter handle."""
    global _installed
    if os.environ.get("SHADOW_TPU_STDERR_FILTER", "1") == "0":
        return None
    if _installed is None:
        _installed = _FdFilter(fd)
    return _installed
