"""Device time of one program's ops by the named scope they were traced
under: the program opens ``jax.named_scope``s (``mla.attend``,
``moe.held``, ...) and each op's metadata on the traced profile carries its
scope in its ``tf_op`` stat (``jit(decode)/while/body/moe.held/...:type``).

``scope_seconds(profile_path, module, scope)`` sums the device time, inside
``bench.window``, of the ops of ``module`` (``jit_decode``) whose ``tf_op``
names ``scope`` as one of its path segments, counting leaf ops only, as
``trace_reduce`` counts ops; over the first device plane, as the idle gaps
are.  A profile whose ops carry no such scope gives 0.

``ProfileData`` shows an op's name but not its metadata's stats, so the
``tf_op`` of each op name is read from the file's event metadata with a
small reader of protobuf's wire format (``_event_scopes``): only the
planes' metadata is parsed, their lines are skipped whole.

``host_stats(profile_path, name, keys)`` reads the stats a host span
carries (the engine's ``serve.emit`` carries a decode step's held-expert
counts): one tuple of ``keys`` per span named ``name`` that opens inside
the window and carries them all.
"""

from __future__ import annotations

from bench import trace_reduce

TF_OP = "tf_op"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message: ints for varints and fixed
    widths, bytes for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _event_scopes(path: str) -> dict:
    """``{plane name: {op name: [tf_op, ...]}}`` from an ``.xplane.pb``
    (XSpace: planes = 1; XPlane: name = 2, event_metadata = 4, stat_metadata
    = 5; XEventMetadata: name = 2, display_name = 4, stats = 5; XStat:
    metadata_id = 1, str_value = 5, ref_value = 7)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 4:
                events.append(dict(_fields(value)).get(2, b""))
            elif pf == 5:
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        tf_op = [k for k, v in stat_names.items() if v == TF_OP]
        ops = {}
        for ev in events:
            meta = list(_fields(ev))
            stats = [dict(_fields(v)) for f, v in meta if f == 5]
            scope = [s.get(5) or stat_names.get(s.get(7), "").encode()
                     for s in stats if s.get(1) in tf_op]
            if not scope:
                continue
            for f, v in meta:
                if f in (2, 4) and v:
                    ops.setdefault(bytes(v).decode(), []).append(bytes(scope[0]).decode())
        out[name] = ops
    return out


def _window(profile) -> tuple[int, int]:
    host = [ev for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in trace_reduce._events(line)
            if ev[0] == trace_reduce.WINDOW]
    if len(host) != 1:
        raise ValueError(f"expected one {trace_reduce.WINDOW} annotation, found {len(host)}")
    return host[0][1], host[0][2]


def host_stats(path: str, name: str, keys: tuple) -> list[tuple]:
    """The values of ``keys`` on each host span ``name`` that opens inside
    the window and carries every one of them, in the order they opened."""
    profile = trace_reduce.load(path)
    lo, hi = _window(profile)
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != name or not lo <= ev.start_ns < hi:
                    continue
                stats = dict(ev.stats)
                if all(k in stats for k in keys):
                    out.append((ev.start_ns, tuple(stats[k] for k in keys)))
    return [v for _, v in sorted(out)]


def scope_seconds(path: str, module: str, scope: str) -> tuple[float, int]:
    """(seconds, ops) of ``module``'s leaf ops under ``scope`` inside the
    window, on the first device plane."""
    profile = trace_reduce.load(path)
    scopes = _event_scopes(str(path))
    lo, hi = _window(profile)
    planes = sorted((p.name, p) for p in profile.planes
                    if p.name.startswith("/device:") and "CPU" not in p.name
                    and any(line.name == trace_reduce.OPS_LINE for line in p.lines))
    if not planes:
        return 0.0, 0
    plane_name, plane = planes[0]
    tf_ops = scopes.get(plane_name, {})
    prefix = f"jit({module.removeprefix('jit_')})"
    lines = {line.name: line for line in plane.lines}
    runs = sorted((a, b, trace_reduce.module_name(n))
                  for n, a, b in trace_reduce._events(lines[trace_reduce.MODULES_LINE])) \
        if trace_reduce.MODULES_LINE in lines else []
    events = sorted(trace_reduce._events(lines[trace_reduce.OPS_LINE]), key=lambda e: (e[1], -e[2]))
    total, n = 0, 0
    for k, (name, a, b) in enumerate(events):
        if k + 1 < len(events) and events[k + 1][2] <= b:  # holds the next op
            continue
        if trace_reduce._enclosing(runs, a) != module:
            continue
        ours = [t for t in tf_ops.get(name, ()) if t.startswith(prefix)]
        if not ours or scope not in ours[0].split(":", 1)[0].split("/"):
            continue
        a, b = trace_reduce._clip(a, b, lo, hi)
        if b > a:
            total += b - a
            n += 1
    return total * 1e-9, n
