"""Read what ``jax.profiler.ProfileData`` does not expose from an
``.xplane.pb``: the optimized HLO of each program the trace ran, which the
profiler stores in the ``/host:metadata`` plane (stat ``Hlo Proto``).

From it, each instruction's opcode, name stack and creating Python stack
(the module's stack-frame index), keyed by (program, instruction name): the
trace's device events carry only the instruction's text. Only the protobuf
wire format is parsed here; field numbers are those of XLA's ``xplane.proto``
and ``hlo.proto``.
"""
from __future__ import annotations


def _varint(b, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def fields(b):
    """(field number, value) of each field of one serialized message;
    length-delimited values are ``memoryview`` slices."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield f, v


def message(b) -> dict:
    out: dict = {}
    for f, v in fields(b):
        out.setdefault(f, []).append(v)
    return out


def _first(d: dict, f: int, default=None):
    return d[f][0] if f in d else default


def hlo_protos(path: str) -> dict[str, memoryview]:
    """Program name as the trace's module events give it
    (``jit_update(7431...)``) -> serialized ``HloProto``."""
    with open(path, "rb") as fh:
        data = fh.read()
    out = {}
    for f, plane in fields(data):                    # XSpace.planes = 1
        if f != 1:
            continue
        p = message(plane)
        if bytes(_first(p, 2, b"")) != b"/host:metadata":   # XPlane.name
            continue
        stat_names = {}
        for entry in p.get(5, []):                   # stat_metadata map
            sm = message(_first(message(entry), 2, b""))
            stat_names[_first(sm, 1, 0)] = bytes(_first(sm, 2, b"")).decode()
        for entry in p.get(4, []):                   # event_metadata map
            em = message(_first(message(entry), 2, b""))
            name = bytes(_first(em, 2, b"")).decode()
            for st in em.get(5, []):                 # XEventMetadata.stats
                s = message(st)
                if stat_names.get(_first(s, 1, 0)) == "Hlo Proto" and 6 in s:
                    out[name] = _first(s, 6)
    return out


def instructions(hlo_proto) -> dict[str, tuple]:
    """Instruction name -> (opcode, name stack, Python stack innermost first
    as ``(file, function)`` pairs) of one ``HloProto``."""
    mod = message(_first(message(hlo_proto), 1, b""))   # HloProto.hlo_module
    index = message(_first(mod, 17, b""))              # stack_frame_index
    files = [bytes(x).decode() for x in index.get(1, [])]
    funcs = [bytes(x).decode() for x in index.get(2, [])]
    locs = [message(x) for x in index.get(3, [])]
    frames = [message(x) for x in index.get(4, [])]

    def stack(fid: int) -> tuple:
        out = []
        while 0 < fid <= len(frames):
            fr = frames[fid - 1]
            loc = locs[_first(fr, 1, 1) - 1]
            out.append((files[_first(loc, 1, 1) - 1],
                        funcs[_first(loc, 2, 1) - 1]))
            fid = _first(fr, 2, 0)
        return tuple(out)

    out = {}
    for comp in mod.get(3, []):                        # computations
        for ins in message(comp).get(2, []):           # instructions
            d = message(ins)
            meta = message(_first(d, 7, b""))          # OpMetadata
            out[bytes(_first(d, 1, b"")).decode()] = (
                bytes(_first(d, 2, b"")).decode(),
                bytes(_first(meta, 2, b"")).decode(),
                stack(_first(meta, 15, 0)))
    return out
