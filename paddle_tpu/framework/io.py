"""paddle.save / paddle.load parity (ref: python/paddle/framework/io.py (U)).

Two formats, one API:
  * small objects — a single pickle whose tensor leaves are numpy arrays
    (same "nested state_dict" user contract as the reference's .pdparams);
  * large checkpoints — the PTCKPT01 container: a pickled structure header
    followed by raw 64-byte-aligned tensor payloads, written/read through the
    native C++ parallel positional-IO path (paddle_tpu.native pwrite/pread —
    the TPU-era analog of the reference's C++ SaveCombine/LoadCombine ops,
    SURVEY.md §2.2 P27) and loaded zero-copy where possible.

The sharded/distributed checkpoint path (reshard-on-load) lives in
paddle_tpu.distributed.checkpoint on top of this.
"""

from __future__ import annotations

import os
import pickle

import numpy as np

from ..core.tensor import Tensor

_MAGIC = b"PTCKPT01"
_ALIGN = 64
# below this many payload bytes the container's extra syscalls cost more
# than they save
_CONTAINER_THRESHOLD = 1 << 20


class _TensorPayload:
    __slots__ = ("array",)

    def __init__(self, array):
        self.array = array


class _PayloadRef:
    """Placeholder in the pickled header pointing into the payload region."""

    __slots__ = ("index",)

    def __init__(self, index):
        self.index = index


def _to_saveable(obj):
    if isinstance(obj, Tensor):
        return _TensorPayload(np.asarray(obj._data))
    if isinstance(obj, dict):
        return {k: _to_saveable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_saveable(v) for v in obj)
    return obj


def _from_saveable(obj, return_numpy=False):
    if isinstance(obj, _TensorPayload):
        return obj.array if return_numpy else Tensor(obj.array)
    if isinstance(obj, dict):
        return {k: _from_saveable(v, return_numpy) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_from_saveable(v, return_numpy) for v in obj)
    return obj


def _swap_payloads(obj, payloads):
    """_TensorPayload -> _PayloadRef, appending arrays to `payloads`."""
    if isinstance(obj, _TensorPayload):
        payloads.append(np.ascontiguousarray(obj.array))
        return _PayloadRef(len(payloads) - 1)
    if isinstance(obj, dict):
        return {k: _swap_payloads(v, payloads) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_swap_payloads(v, payloads) for v in obj)
    return obj


def _resolve_refs(obj, arrays, return_numpy):
    if isinstance(obj, _PayloadRef):
        a = arrays[obj.index]
        return a if return_numpy else Tensor(a)
    if isinstance(obj, dict):
        return {k: _resolve_refs(v, arrays, return_numpy) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_resolve_refs(v, arrays, return_numpy) for v in obj)
    return obj


def _save_container(saveable, path, protocol):
    payloads = []
    structure = _swap_payloads(saveable, payloads)
    metas = []
    offset = 0
    for a in payloads:
        offset = (offset + _ALIGN - 1) // _ALIGN * _ALIGN
        metas.append((str(a.dtype), a.shape, offset, a.nbytes))
        offset += a.nbytes
    header = pickle.dumps({"structure": structure, "metas": metas},
                          protocol=protocol)
    preamble = _MAGIC + len(header).to_bytes(8, "little") + header
    payload_start = (len(preamble) + _ALIGN - 1) // _ALIGN * _ALIGN
    total = payload_start + offset

    # write to a temp file and os.replace so an interrupted save can never
    # leave a structurally-valid-but-zero checkpoint for autoresume to load
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(preamble)
        f.truncate(total)

    from .. import native

    for a, (_, _, off, nbytes) in zip(payloads, metas):
        if nbytes == 0:
            continue
        if not native.pwrite(tmp, payload_start + off, a):
            with open(tmp, "r+b") as f:  # no native toolchain: plain IO
                f.seek(payload_start + off)
                f.write(a.tobytes())
    os.replace(tmp, path)


def _load_container(path, return_numpy):
    with open(path, "rb") as f:
        f.seek(len(_MAGIC))
        header_len = int.from_bytes(f.read(8), "little")
        header = pickle.loads(f.read(header_len))
        preamble_len = len(_MAGIC) + 8 + header_len
    payload_start = (preamble_len + _ALIGN - 1) // _ALIGN * _ALIGN

    from .. import native

    arrays = []
    use_native = native.available()
    mm = None
    if not use_native:
        mm = np.memmap(path, dtype=np.uint8, mode="r")
    for dtype_str, shape, off, nbytes in header["metas"]:
        out = np.empty(shape, dtype=np.dtype(dtype_str))
        if nbytes:
            if use_native:
                flat = out.reshape(-1).view(np.uint8)
                native.pread(path, payload_start + off, flat)
            else:
                raw = mm[payload_start + off: payload_start + off + nbytes]
                # copy into the writable buffer (frombuffer views are
                # read-only, unlike every other load path)
                out.reshape(-1).view(np.uint8)[:] = raw
        arrays.append(out)
    return _resolve_refs(header["structure"], arrays, return_numpy)


def _payload_bytes(obj):
    if isinstance(obj, _TensorPayload):
        return obj.array.nbytes
    if isinstance(obj, dict):
        return sum(_payload_bytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_payload_bytes(v) for v in obj)
    return 0


def save(obj, path, protocol=4, **configs):
    # checkpoint saves land in the span log (one record a save, and the
    # count and seconds of ``span.seconds``), so "why did step time
    # spike" is answerable when the answer is "a checkpoint flushed"
    from ..observability.span import span as _obs_span

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    saveable = _to_saveable(obj)
    nbytes = _payload_bytes(saveable)
    with _obs_span("checkpoint.save", cat="io", path=str(path),
                   payload_bytes=nbytes):
        if nbytes >= _CONTAINER_THRESHOLD:
            _save_container(saveable, path, protocol)
            return
        with open(path, "wb") as f:
            pickle.dump(saveable, f, protocol=protocol)


def load(path, return_numpy=False, **configs):
    with open(path, "rb") as f:
        magic = f.read(len(_MAGIC))
    if magic == _MAGIC:
        return _load_container(path, return_numpy)
    with open(path, "rb") as f:
        obj = pickle.load(f)
    return _from_saveable(obj, return_numpy=return_numpy)
