"""Write small XSpace traces by hand for the trace tests: the protobuf wire
format with the field numbers ``chipbench/lib/xplane.py`` reads."""
from __future__ import annotations


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def f_int(num: int, v: int) -> bytes:
    return varint(num << 3) + varint(v)


def f_msg(num: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(num << 3 | 2) + varint(len(payload)) + payload


def event(meta_id: int, start_ns: int, dur_ns: int) -> bytes:
    return f_msg(4, f_int(1, meta_id) + f_int(2, start_ns * 1000)
                 + f_int(3, dur_ns * 1000))


def line(line_id: int, name: str, events: list) -> bytes:
    return f_msg(3, f_int(1, line_id) + f_msg(2, name) + f_int(3, 0)
                 + b"".join(events))


def event_meta(meta_id: int, name: str, stats: bytes = b"") -> bytes:
    value = f_int(1, meta_id) + f_msg(2, name) + stats
    return f_msg(4, f_int(1, meta_id) + f_msg(2, value))


def plane(plane_id: int, name: str, lines: list, metas: list,
          stat_metas: bytes = b"") -> bytes:
    return f_msg(1, f_int(1, plane_id) + f_msg(2, name) + b"".join(lines)
                 + b"".join(metas) + stat_metas)


def hlo_proto(instructions: list, frames: list) -> bytes:
    """``instructions``: (name, opcode, frame id); ``frames``: innermost
    first lists of (file, function); frame ids count from 1 in the order
    the chains are laid out."""
    files, funcs, locs, stack_frames, ids = [], [], [], [], []
    for chain in frames:
        parent = 0
        for file, fn in reversed(chain):      # outermost first
            if file not in files:
                files.append(file)
            if fn not in funcs:
                funcs.append(fn)
            locs.append(f_int(1, files.index(file) + 1)
                        + f_int(2, funcs.index(fn) + 1) + f_int(3, 1))
            stack_frames.append(f_int(1, len(locs)) + f_int(2, parent))
            parent = len(stack_frames)
        ids.append(parent)
    index = (b"".join(f_msg(1, x) for x in files)
             + b"".join(f_msg(2, x) for x in funcs)
             + b"".join(f_msg(3, x) for x in locs)
             + b"".join(f_msg(4, x) for x in stack_frames))
    insts = b"".join(
        f_msg(2, f_msg(1, name) + f_msg(2, opcode)
              + f_msg(7, f_msg(2, "jit(update)/op")
                      + f_int(15, ids[frame - 1] if frame else 0)))
        for name, opcode, frame in instructions)
    module = f_msg(1, "jit_update") + f_msg(3, f_msg(1, "main") + insts) \
        + f_msg(17, index)
    return f_msg(1, module)


def metadata_plane(plane_id: int, programs: dict) -> bytes:
    stat_meta = f_msg(5, f_int(1, 1) + f_msg(2, f_int(1, 1) + f_msg(2, "Hlo Proto")))
    metas = [event_meta(i + 100, name, f_msg(5, f_int(1, 1) + f_msg(6, proto)))
             for i, (name, proto) in enumerate(programs.items())]
    return plane(plane_id, "/host:metadata", [], metas, stat_meta)
