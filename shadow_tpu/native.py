"""ctypes binding to the native C++ runtime (native/).

Loads (building on first use if needed) libshadowtpu_native.so: the
shared-memory arena with buddy allocation + serializable handles, and
the spinning-semaphore IPC channel — the substrate the managed-process
runtime (syscall interposition) is built on, mirroring the role of the
reference's shmem allocator + shim IPC.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libshadowtpu_native.so")

_lib: Optional[ctypes.CDLL] = None


class IpcMessage(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_uint32),
        ("_pad", ctypes.c_uint32),
        ("number", ctypes.c_int64),
        ("args", ctypes.c_uint64 * 6),
        ("inline_bytes", ctypes.c_uint8 * 64),
    ]


IPC_NONE = 0
IPC_START = 1
IPC_SYSCALL = 2
IPC_SYSCALL_DONE = 3
IPC_SYSCALL_NATIVE = 4
IPC_STOP = 5
IPC_CLONE_GO = 6       # sim->plugin: clone approved (vtid + chan offset)
IPC_EXEC_DONE = 12     # plugin->sim: post-execve image live on channel
IPC_THREAD_START = 7   # child thread announcing itself on its channel
IPC_THREAD_FAIL = 8    # native clone failed after approval
IPC_FORK_RESULT = 9    # parent->sim: real child pid (or -errno)
IPC_SIGNAL = 10        # sim->plugin: run handler args[0] for signal
IPC_SIGNAL_DONE = 11   # plugin->sim: handler returned


_made: dict = {}


def _make(target: str, check: bool = True) -> bool:
    """Bring one ``native/build`` target up to date, once per process.
    ``make`` is a no-op when the target is current and rebuilds it
    when a ``native/`` source changed, so a stale build that came
    along with the checkout is never used. Returns whether it
    succeeded (with ``check``, a failure raises)."""
    if target not in _made:
        r = subprocess.run(["make", "-C", _NATIVE_DIR, target],
                           capture_output=True)
        if check and r.returncode:
            raise subprocess.CalledProcessError(
                r.returncode, r.args, r.stdout, r.stderr)
        _made[target] = r.returncode == 0
    return _made[target]


def load() -> ctypes.CDLL:
    """Load the native library, building it on first use."""
    global _lib
    if _lib is not None:
        return _lib
    _make("build/libshadowtpu_native.so")
    lib = ctypes.CDLL(_LIB_PATH)
    lib.shadowtpu_arena_create.restype = ctypes.c_void_p
    lib.shadowtpu_arena_create.argtypes = [ctypes.c_char_p,
                                           ctypes.c_uint64]
    lib.shadowtpu_arena_open.restype = ctypes.c_void_p
    lib.shadowtpu_arena_open.argtypes = [ctypes.c_char_p]
    lib.shadowtpu_arena_close.argtypes = [ctypes.c_void_p]
    lib.shadowtpu_arena_unlink.argtypes = [ctypes.c_void_p]
    lib.shadowtpu_arena_alloc.restype = ctypes.c_void_p
    lib.shadowtpu_arena_alloc.argtypes = [ctypes.c_void_p,
                                          ctypes.c_uint64]
    lib.shadowtpu_arena_free.argtypes = [ctypes.c_void_p,
                                         ctypes.c_void_p]
    lib.shadowtpu_arena_allocated.restype = ctypes.c_uint64
    lib.shadowtpu_arena_allocated.argtypes = [ctypes.c_void_p]
    lib.shadowtpu_arena_offset.restype = ctypes.c_uint64
    lib.shadowtpu_arena_offset.argtypes = [ctypes.c_void_p,
                                           ctypes.c_void_p]
    lib.shadowtpu_arena_at.restype = ctypes.c_void_p
    lib.shadowtpu_arena_at.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.shadowtpu_cleanup_orphans.restype = ctypes.c_int
    lib.shadowtpu_cleanup_orphans.argtypes = [ctypes.c_char_p]
    lib.shadowtpu_ipc_sizeof.restype = ctypes.c_uint64
    lib.shadowtpu_ipc_init.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.shadowtpu_ipc_send_to_plugin.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(IpcMessage)]
    lib.shadowtpu_ipc_set_sim_now.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64]
    lib.shadowtpu_ipc_recv_from_plugin.restype = ctypes.c_int
    lib.shadowtpu_ipc_recv_from_plugin.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(IpcMessage)]
    lib.shadowtpu_ipc_recv_from_plugin_timed.restype = ctypes.c_int
    lib.shadowtpu_ipc_recv_from_plugin_timed.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(IpcMessage), ctypes.c_uint32]
    lib.shadowtpu_ipc_send_to_simulator.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(IpcMessage)]
    lib.shadowtpu_ipc_recv_from_simulator.restype = ctypes.c_int
    lib.shadowtpu_ipc_recv_from_simulator.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(IpcMessage)]
    lib.shadowtpu_ipc_mark_plugin_exited.argtypes = [ctypes.c_void_p]
    lib.shadowtpu_ipc_native_thread_alive.restype = ctypes.c_uint32
    lib.shadowtpu_ipc_native_thread_alive.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


class ShmArena:
    """Python handle to a native shared-memory arena."""

    def __init__(self, name: str, size: int = 0, create: bool = True):
        self._lib = load()
        self.name = name
        if create:
            self._h = self._lib.shadowtpu_arena_create(
                name.encode(), size)
        else:
            self._h = self._lib.shadowtpu_arena_open(name.encode())
        if not self._h:
            raise OSError(f"cannot {'create' if create else 'open'} "
                          f"arena {name}")

    def alloc(self, nbytes: int) -> int:
        p = self._lib.shadowtpu_arena_alloc(self._h, nbytes)
        if not p:
            raise MemoryError(f"arena {self.name} exhausted")
        return p

    def free(self, p: int) -> None:
        self._lib.shadowtpu_arena_free(self._h, p)

    @property
    def allocated(self) -> int:
        return self._lib.shadowtpu_arena_allocated(self._h)

    def offset_of(self, p: int) -> int:
        return self._lib.shadowtpu_arena_offset(self._h, p)

    def at_offset(self, off: int) -> int:
        return self._lib.shadowtpu_arena_at(self._h, off)

    def unlink(self) -> None:
        self._lib.shadowtpu_arena_unlink(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.shadowtpu_arena_close(self._h)
            self._h = None


class IpcChannel:
    """An IPC channel living inside an arena at a known offset."""

    def __init__(self, arena: ShmArena, ptr: Optional[int] = None,
                 spin_max: int = 8096):
        self._lib = load()
        self.arena = arena
        if ptr is None:
            ptr = arena.alloc(self._lib.shadowtpu_ipc_sizeof())
            self._lib.shadowtpu_ipc_init(ptr, spin_max)
        self.ptr = ptr

    @property
    def offset(self) -> int:
        return self.arena.offset_of(self.ptr)

    def send_to_plugin(self, msg: IpcMessage) -> None:
        self._lib.shadowtpu_ipc_send_to_plugin(self.ptr,
                                               ctypes.byref(msg))

    def set_sim_now(self, now_ns: int) -> None:
        """Publish simulated time for the shim's passive readers
        (log timestamps; ref shim_event.h:17-22 sim_time block)."""
        self._lib.shadowtpu_ipc_set_sim_now(self.ptr, now_ns)

    def recv_from_plugin(self) -> Optional[IpcMessage]:
        out = IpcMessage()
        ok = self._lib.shadowtpu_ipc_recv_from_plugin(
            self.ptr, ctypes.byref(out))
        return out if ok else None

    def recv_from_plugin_timed(self, timeout_ms: int
                               ) -> tuple[int, Optional[IpcMessage]]:
        """-> (status, msg): 1 = received, 0 = plugin exited,
        -1 = timed out."""
        out = IpcMessage()
        status = self._lib.shadowtpu_ipc_recv_from_plugin_timed(
            self.ptr, ctypes.byref(out), timeout_ms)
        return status, (out if status == 1 else None)

    def send_to_simulator(self, msg: IpcMessage) -> None:
        self._lib.shadowtpu_ipc_send_to_simulator(self.ptr,
                                                  ctypes.byref(msg))

    def recv_from_simulator(self) -> Optional[IpcMessage]:
        out = IpcMessage()
        ok = self._lib.shadowtpu_ipc_recv_from_simulator(
            self.ptr, ctypes.byref(out))
        return out if ok else None

    def mark_plugin_exited(self) -> None:
        self._lib.shadowtpu_ipc_mark_plugin_exited(self.ptr)

    def native_thread_alive(self) -> bool:
        """True while the cloned native thread behind this channel is
        alive (kernel-cleared CLEARTID guard; see spinsem.hpp)."""
        return bool(self._lib.shadowtpu_ipc_native_thread_alive(self.ptr))


def cleanup_orphans(prefix: str = "shadowtpu_shm_") -> int:
    return load().shadowtpu_cleanup_orphans(prefix.encode())


_SHIM_PATH = os.path.join(_NATIVE_DIR, "build", "libshadowtpu_shim.so")


def shim_path() -> str:
    """Path to the preload shim injected into managed processes."""
    _make("build/libshadowtpu_shim.so")
    return _SHIM_PATH


_LAUNCHER_PATH = os.path.join(_NATIVE_DIR, "build",
                              "shadowtpu_launcher")


def launcher_path() -> str:
    """Path to the ptrace-backend tracee launcher stub."""
    _make("build/shadowtpu_launcher")
    return _LAUNCHER_PATH


_LAUNCHER_STATIC_PATH = os.path.join(_NATIVE_DIR, "build",
                                     "shadowtpu_launcher_static")


def launcher_static_path():
    """Path to the STATIC launcher stub (preload backend's --run
    mode: rlimit cap + ASLR off + exec, with LD_PRELOAD inert in the
    stub itself), or None when no static libc exists on this machine
    (callers fall back to a preexec_fn). The build attempt is
    memoized — a machine without static libc must not pay a failing
    make per process spawn."""
    if _make("build/shadowtpu_launcher_static", check=False):
        return _LAUNCHER_STATIC_PATH
    return None
