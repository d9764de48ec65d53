"""TPU shared-memory transport: HBM-resident tensor regions.

This is the framework's replacement for the reference's CUDA IPC shared
memory (reference src/c++/library/ipc.h:28-33 and
tritonclient/utils/cuda_shared_memory/ — cudaMalloc + cudaIpcGetMemHandle +
native libccudashm.so): a *device-buffer registry* over JAX/PJRT instead of
cudart, backed by the native ``libctpushm.so`` (src/cpp/shm/ctpushm.cc).

Design (SURVEY.md §2.2/§5.8).  A region has two coupled faces:

- **HBM face** — ``jax.Array`` slots keyed by byte offset.  When client and
  server share a process (in-process server, the triton_c_api analog) the
  server resolves the region through a process-local broker and reads/writes
  the device arrays directly: true zero-copy, no H2D/D2H per request, and
  inference dispatch stays asynchronous.
- **Host window (native)** — a POSIX-shm-backed byte-addressable buffer
  managed by ``libctpushm.so``.  Every region has one; it is the region's
  process-portable face (PJRT has no cudaIpc-style cross-process HBM
  export).  Reads and writes work at *any* byte offset.  Device-side writes
  mark their range dirty and are synced to the window lazily, on first byte
  read — so the async zero-copy path never pays a hidden D2H.

The raw handle (the ``cudaIpcMemHandle_t`` analog, JSON emitted by the
native library): ``{"uuid", "pid", "device_id", "byte_size", "staging_key"}``
where ``staging_key`` is the window's POSIX shm key.

Reads with ``get_contents_as_numpy`` force a D2H sync of dirty ranges;
``get_contents_as_jax`` returns the live device array without synchronizing.
"""

import ctypes
import json
import os
import threading

import numpy as np

from client_tpu.utils import (
    InferenceServerException,
    serialize_byte_tensor,
    triton_to_np_dtype,
)

# Process-local broker: uuid -> TpuRegion.  The in-process server resolves
# raw handles here (the PJRT same-process fast path).
_broker = {}
_broker_lock = threading.Lock()

_LIB_PATH = os.path.join(os.path.dirname(__file__), "libctpushm.so")
_lib = None
_lib_lock = threading.Lock()


def _load():
    global _lib
    with _lib_lock:
        if _lib is None:
            if not os.path.exists(_LIB_PATH):
                raise InferenceServerException(
                    f"native TPU shared-memory library not built: {_LIB_PATH} "
                    "(run `make native`)"
                )
            lib = ctypes.CDLL(_LIB_PATH)
            lib.TpuHbmRegionCreate.restype = ctypes.c_void_p
            lib.TpuHbmRegionCreate.argtypes = [ctypes.c_uint64, ctypes.c_int]
            lib.TpuHbmRegionOpen.restype = ctypes.c_void_p
            lib.TpuHbmRegionOpen.argtypes = [ctypes.c_char_p]
            lib.TpuHbmWrite.restype = ctypes.c_int
            lib.TpuHbmWrite.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
            ]
            lib.TpuHbmRead.restype = ctypes.c_int
            lib.TpuHbmRead.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
            ]
            lib.TpuHbmGetRawHandle.restype = ctypes.c_int
            lib.TpuHbmGetRawHandle.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
            ]
            lib.TpuHbmRegionDestroy.restype = ctypes.c_int
            lib.TpuHbmRegionDestroy.argtypes = [ctypes.c_void_p]
            lib.TpuHbmLastError.restype = ctypes.c_char_p
            _lib = lib
    return _lib


def _last_error(lib):
    msg = lib.TpuHbmLastError()
    return msg.decode("utf-8", errors="replace") if msg else "unknown error"


def _jax():
    import jax  # deferred so pure-protocol users never pay jax import cost

    return jax


class _Window:
    """ctypes wrapper over one native host-window handle.

    The library handle is resolved once at construction; per-operation calls
    never touch the global loader lock.  Negative offsets/sizes are rejected
    here before they can wrap through the unsigned native ABI.
    """

    def __init__(self, lib, handle, byte_size):
        self._lib = lib
        self._handle = handle
        self.byte_size = byte_size

    @classmethod
    def create(cls, byte_size, device_id):
        lib = _load()
        handle = lib.TpuHbmRegionCreate(byte_size, device_id)
        if not handle:
            raise InferenceServerException(
                f"TpuHbmRegionCreate failed: {_last_error(lib)}"
            )
        return cls(lib, handle, byte_size)

    @classmethod
    def open(cls, raw_handle, byte_size):
        lib = _load()
        if isinstance(raw_handle, str):
            raw_handle = raw_handle.encode("utf-8")
        handle = lib.TpuHbmRegionOpen(raw_handle)
        if not handle:
            raise InferenceServerException(
                f"TpuHbmRegionOpen failed: {_last_error(lib)}"
            )
        return cls(lib, handle, byte_size)

    def _live(self):
        if self._handle is None:
            raise InferenceServerException("TPU region window is closed")
        return self._handle

    def write(self, offset, data):
        if offset < 0:
            raise InferenceServerException(f"negative offset {offset}")
        # bytearray must be converted too: ctypes c_void_p rejects it
        buf = data if isinstance(data, bytes) else bytes(data)
        rc = self._lib.TpuHbmWrite(self._live(), offset, buf, len(buf))
        if rc != 0:
            raise InferenceServerException(
                f"TpuHbmWrite failed ({rc}): {_last_error(self._lib)}"
            )

    def read(self, offset, nbytes):
        if offset < 0 or nbytes < 0:
            raise InferenceServerException(
                f"negative offset/size ({offset}, {nbytes})"
            )
        out = ctypes.create_string_buffer(nbytes) if nbytes else b""
        if nbytes == 0:
            return b""
        rc = self._lib.TpuHbmRead(self._live(), offset, out, nbytes)
        if rc != 0:
            raise InferenceServerException(
                f"TpuHbmRead failed ({rc}): {_last_error(self._lib)}"
            )
        return out.raw

    def raw_handle(self):
        buf = ctypes.create_string_buffer(512)
        n = self._lib.TpuHbmGetRawHandle(self._live(), buf, 512)
        if n < 0:
            raise InferenceServerException(
                f"TpuHbmGetRawHandle failed ({n}): {_last_error(self._lib)}"
            )
        return buf.raw[:n]

    def destroy(self):
        if self._handle is not None:
            self._lib.TpuHbmRegionDestroy(self._handle)
            self._handle = None


class TpuRegion:
    """One named HBM region: device-array slots + native byte window."""

    def __init__(self, name, byte_size, device_id):
        self.name = name
        self.byte_size = byte_size
        self.device_id = device_id
        self._window = _Window.create(byte_size, device_id)
        desc = json.loads(self._window.raw_handle())
        self.uuid = desc["uuid"]
        self.staging_key = desc["staging_key"]
        self._slots = {}  # offset -> jax.Array | np.ndarray (BYTES only)
        self._dirty = set()  # offsets whose window bytes are stale
        self._lock = threading.Lock()

    # -- slot access --------------------------------------------------------

    def _device(self):
        jax = _jax()
        try:
            devs = jax.devices()
        except RuntimeError as e:
            # a TPU belongs to one process at a time: against a standalone
            # server that already holds the chip, this is where a client
            # process finds out
            raise InferenceServerException(
                f"TPU region '{self.name}': this process cannot open a JAX "
                f"device ({e}). If a server on this host holds the chip, "
                "run the client with JAX_PLATFORMS=cpu (the region then "
                "stages in host memory and reaches the server through its "
                "host window) or serve in-process (--hermetic)."
            ) from e
        if self.device_id >= len(devs):
            raise InferenceServerException(
                f"TPU device {self.device_id} not present ({len(devs)} devices)"
            )
        return devs[self.device_id]

    def write_array(self, offset, arr):
        """Place a tensor at ``offset``; device_put unless already on device.

        Host tensors mirror their bytes into the window immediately (cheap
        memcpy); device tensors only mark the range dirty — the D2H happens
        lazily on the first byte-level read, never on the dispatch path.
        """
        jax = _jax()
        host_bytes = None
        if isinstance(arr, np.ndarray) and arr.dtype == np.object_:
            raw = serialize_byte_tensor(arr)
            host_bytes = raw.tobytes()
            nbytes = len(host_bytes)
            stored = arr  # BYTES stay host-side; devices hold no string type
        elif isinstance(arr, jax.Array):
            nbytes = arr.dtype.itemsize * int(np.prod(arr.shape))
            stored = arr
        else:
            arr = np.ascontiguousarray(arr)
            host_bytes = arr.tobytes()
            nbytes = len(host_bytes)
            stored = jax.device_put(arr, self._device())
        if offset < 0 or offset + nbytes > self.byte_size:
            raise InferenceServerException(
                f"write of {nbytes} bytes at offset {offset} overruns TPU "
                f"region '{self.name}' ({self.byte_size} bytes)"
            )
        with self._lock:
            # drop slots this write fully or partially overlaps; a dirty slot
            # only PARTIALLY covered is flushed to the window first so its
            # non-overlapped bytes survive (the byte-addressable contract).
            # A fully-covered slot is simply replaced — flushing it would put
            # a hidden D2H on the hot full-overwrite path (every per-request
            # output write lands at the same offset/size).
            for off, old in list(self._slots.items()):
                old_n = _slot_nbytes(old)
                if off < offset + nbytes and offset < off + old_n:
                    if off in self._dirty and not (
                        offset <= off and off + old_n <= offset + nbytes
                    ):
                        self._flush_slot_locked(off, old)
                    del self._slots[off]
                    self._dirty.discard(off)
            self._slots[offset] = stored
            if host_bytes is not None:
                self._window.write(offset, host_bytes)
            else:
                self._dirty.add(offset)
        return nbytes

    def read(self, offset, nbytes):
        """Byte-addressable read at any offset (syncs dirty device slots).

        The D2H transfer of dirty slots happens OUTSIDE the region lock:
        concurrent readers (e.g. perf-harness completion-sync workers all
        polling the same output region) each wait for their own transfer in
        parallel instead of serializing behind one lock-held transfer."""
        if offset < 0 or nbytes < 0 or offset + nbytes > self.byte_size:
            raise InferenceServerException(
                f"read of {nbytes} bytes at offset {offset} overruns TPU "
                f"region '{self.name}' ({self.byte_size} bytes)"
            )
        with self._lock:
            base = self._window.read(offset, nbytes)
            snaps = [
                (off, self._slots[off])
                for off in sorted(self._dirty)
                if off in self._slots
                and off < offset + nbytes
                and offset < off + _slot_nbytes(self._slots[off])
            ]
            for off in list(self._dirty):
                if off not in self._slots:
                    self._dirty.discard(off)
        if not snaps:
            return base
        # D2H outside the lock — concurrent readers transfer in parallel —
        # then overlay the snapshot bytes over the window view locally.  The
        # reader observes the region as of read start even if writers keep
        # re-dirtying the same offsets (the old settle-under-the-lock loop
        # could chase a continuously-rewritten slot for seconds while
        # serializing every other reader behind it).
        flushed = [
            (off, slot, np.ascontiguousarray(np.asarray(slot)).tobytes())
            for off, slot in snaps
        ]
        buf = bytearray(base)
        for off, slot, host in flushed:
            lo = max(off, offset)
            hi = min(off + len(host), offset + nbytes)
            if lo < hi:
                buf[lo - offset : hi - offset] = host[lo - off : hi - off]
        with self._lock:
            # opportunistic write-back: only what no concurrent write replaced
            for off, slot, host in flushed:
                if self._slots.get(off) is slot and off in self._dirty:
                    self._window.write(off, host)
                    self._dirty.discard(off)
        return bytes(buf)

    def write(self, offset, data):
        """Byte-addressable write (drops any device slots it overlaps)."""
        if offset < 0 or offset + len(data) > self.byte_size:
            raise InferenceServerException(
                f"write of {len(data)} bytes at offset {offset} overruns TPU "
                f"region '{self.name}' ({self.byte_size} bytes)"
            )
        with self._lock:
            for off, old in list(self._slots.items()):
                old_n = _slot_nbytes(old)
                if off < offset + len(data) and offset < off + old_n:
                    # flush only partially-covered dirty slots (see write_array)
                    if off in self._dirty and not (
                        offset <= off and off + old_n <= offset + len(data)
                    ):
                        self._flush_slot_locked(off, old)
                    del self._slots[off]
                    self._dirty.discard(off)
            self._window.write(offset, data)

    def _flush_slot_locked(self, off, slot):
        """D2H-sync one device slot's bytes into the window (lock held)."""
        host = np.asarray(slot)
        self._window.write(off, np.ascontiguousarray(host).tobytes())

    def _sync_dirty(self, offset, nbytes):
        """Flush dirty device slots overlapping [offset, offset+nbytes) into
        the window.  Caller holds self._lock."""
        for off in sorted(self._dirty):
            slot = self._slots.get(off)
            if slot is None:
                self._dirty.discard(off)
                continue
            n = _slot_nbytes(slot)
            if off < offset + nbytes and offset < off + n:
                self._flush_slot_locked(off, slot)
                self._dirty.discard(off)

    def read_array(self, offset, byte_size, datatype=None, shape=None):
        """Zero-copy read when the stored device array at ``offset`` matches;
        byte-window reconstruction for any other offset/dtype/shape."""
        with self._lock:
            a = self._slots.get(offset)
        if datatype is None:
            if a is None:
                raise InferenceServerException(
                    f"no tensor at offset {offset} of TPU region '{self.name}'"
                )
            return a
        if datatype == "BYTES":
            if isinstance(a, np.ndarray) and a.dtype == np.object_:
                return a.reshape(shape) if shape is not None else a
            from client_tpu.utils import deserialize_bytes_tensor

            raw = self.read(offset, byte_size or self.byte_size - offset)
            # cap at shape-many elements: the region's tail past the tensor
            # is arbitrary bytes, not length-prefixed data (a 0-d shape []
            # caps at 1 element, matching the `shape is not None` reshape)
            n = int(np.prod(shape)) if shape is not None else None
            arr = deserialize_bytes_tensor(raw, max_elements=n)
            if n is not None and arr.size < n:
                raise InferenceServerException(
                    f"region holds {arr.size} BYTES elements, need {n}"
                )
            return arr.reshape(shape) if shape is not None else arr
        np_dtype = triton_to_np_dtype(datatype)
        if np_dtype is None:
            raise InferenceServerException(f"unsupported datatype {datatype}")
        want = np.dtype(np_dtype)
        if (
            a is not None
            and hasattr(a, "dtype")
            and a.dtype == want
            and (shape is None or list(a.shape) == list(shape))
        ):
            return a  # zero-copy device array
        # any other offset/dtype/shape: reconstruct from window bytes
        raw = self.read(offset, byte_size)
        out = np.frombuffer(raw, dtype=want)
        return out.reshape(shape) if shape is not None else out

    def destroy(self):
        with self._lock:
            self._slots.clear()
            self._dirty.clear()
            self._window.destroy()

    def raw_handle(self):
        return self._window.raw_handle()


class TpuWindowRegion:
    """Server-side attachment to a foreign process's region: byte window
    only (the HBM face is not exportable across processes — reads
    reconstruct from bytes, writes land in the window)."""

    def __init__(self, descriptor):
        self.descriptor = descriptor
        self.byte_size = descriptor["byte_size"]
        self._window = _Window.open(json.dumps(descriptor), self.byte_size)
        self._lock = threading.Lock()

    def read(self, offset, nbytes):
        if offset < 0 or nbytes < 0 or offset + nbytes > self.byte_size:
            raise InferenceServerException(
                f"read of {nbytes} bytes at offset {offset} overruns TPU "
                "region window"
            )
        with self._lock:
            return self._window.read(offset, nbytes)

    def write(self, offset, data):
        if offset < 0 or offset + len(data) > self.byte_size:
            raise InferenceServerException(
                f"write of {len(data)} bytes at offset {offset} overruns TPU "
                "region window"
            )
        with self._lock:
            self._window.write(offset, data)

    def read_array(self, offset, byte_size, datatype=None, shape=None):
        from client_tpu.utils import from_wire_bytes

        raw = self.read(offset, byte_size)
        return from_wire_bytes(raw, datatype, shape)

    def write_array(self, offset, arr):
        from client_tpu.utils import np_to_triton_dtype, to_wire_bytes

        host = np.asarray(arr)
        raw = to_wire_bytes(host, np_to_triton_dtype(host.dtype))
        self.write(offset, raw)
        return len(raw)

    def close(self):
        # same lock as read/write: a concurrent request can never race the
        # munmap (use-after-unmap); late calls see a closed-window error
        with self._lock:
            self._window.destroy()


def resolve_inprocess(descriptor):
    """Server-side: map a raw-handle descriptor to a live TpuRegion when the
    client shares this process; None otherwise."""
    if descriptor.get("pid") != os.getpid():
        return None
    with _broker_lock:
        return _broker.get(descriptor.get("uuid"))


# -- public API (parity with cuda_shared_memory/__init__.py:46-120) ---------


def create_shared_memory_region(triton_shm_name, byte_size, device_id=0,
                                staging_key=None):
    """Allocate a TPU HBM region (device slots + native host window).

    ``staging_key`` is accepted for backward compatibility and ignored: every
    region now has a native window whose shm key rides the raw handle.
    """
    region = TpuRegion(triton_shm_name, byte_size, device_id)
    with _broker_lock:
        _broker[region.uuid] = region
    return region


def get_raw_handle(shm_handle):
    """Serializable descriptor to pass to register_tpu_shared_memory."""
    return shm_handle.raw_handle()


def set_shared_memory_region(shm_handle, input_values, offset=0):
    """Copy a list of tensors (numpy or jax.Array) into the region
    back-to-back starting at ``offset``."""
    if not isinstance(input_values, (list, tuple)):
        raise InferenceServerException("input_values must be a list of tensors")
    cur = offset
    for arr in input_values:
        cur += shm_handle.write_array(cur, arr)


def get_contents_as_numpy(shm_handle, datatype, shape, offset=0):
    """Materialize the tensor at ``offset`` host-side (forces D2H sync of
    dirty device slots overlapping the range)."""
    if isinstance(datatype, str):
        wire = datatype
    else:
        from client_tpu.utils import np_to_triton_dtype

        wire = np_to_triton_dtype(np.dtype(datatype))
    count = int(np.prod(shape)) if len(shape) else 1
    if wire == "BYTES":
        return shm_handle.read_array(offset, 0, "BYTES", shape)
    itemsize = np.dtype(triton_to_np_dtype(wire)).itemsize
    arr = shm_handle.read_array(offset, count * itemsize, wire, list(shape))
    return np.asarray(arr)


def get_contents_as_jax(shm_handle, offset=0):
    """The live device array at ``offset`` — no synchronization, no copy."""
    return shm_handle.read_array(offset, 0)


def allocated_shared_memory_regions():
    with _broker_lock:
        return [r.name for r in _broker.values()]


def destroy_shared_memory_region(shm_handle):
    with _broker_lock:
        _broker.pop(shm_handle.uuid, None)
    shm_handle.destroy()


def _slot_nbytes(a):
    if isinstance(a, np.ndarray) and a.dtype == np.object_:
        return serialize_byte_tensor(a).nbytes
    return a.dtype.itemsize * int(np.prod(a.shape))
