"""What the program's own spans and named scopes attribute in a profiler
trace, beside the reduction in trace.py (which it reads through):

- each device op's framework path, the `tf_op` stat that XLA keeps on the
  op's event *metadata* in the `.xplane.pb`. `jax.profiler.ProfileData`
  shows an event's own stats only, so a plain protobuf wire-format walk of
  XSpace -> XPlane.event_metadata / stat_metadata reads it; the names of
  `jax.named_scope`s are components of that path;
- device self time per scope, over the executions of one program;
- host seconds per span name, and device program executions inside spans.
"""

from __future__ import annotations

from . import trace

#: The label of device ops whose path holds none of the scopes asked for.
UNSCOPED = "unscoped"

# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 (map),
# stat_metadata = 5 (map); XLine: name = 2, events = 4; XEvent:
# metadata_id = 1; XEventMetadata: id = 1, name = 2, stats = 5;
# XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1, str_value = 5;
# a map entry: key = 1, value = 2 (tensorflow/tsl/profiler/protobuf/xplane.proto).


def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes):
    """(field number, value) of one protobuf message: an int for a varint,
    bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map(entries) -> dict:
    """{key: value message} of a protobuf map's entries."""
    out = {}
    for entry in entries:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def _op_paths(plane: bytes) -> list:
    """[(event name, tf_op)] of the plane's "XLA Ops" line, in line order."""
    name_of_stat = {k: dict(_fields(v)).get(2, b"").decode()
                    for k, v in _map(v for f, v in _fields(plane)
                                     if f == 5).items()}
    meta = {}
    for k, v in _map(v for f, v in _fields(plane) if f == 4).items():
        name, tf_op = "", ""
        for f, x in _fields(v):
            if f == 2:
                name = x.decode()
            elif f == 5:
                st = dict(_fields(x))
                if name_of_stat.get(st.get(1)) == "tf_op":
                    tf_op = st.get(5, b"").decode()
        meta[k] = (name, tf_op)
    for f, line in _fields(plane):
        if f == 3:
            fields = list(_fields(line))
            if any(g == 2 and x.decode() == trace.OPS_LINE for g, x in fields):
                return [meta.get(dict(_fields(ev)).get(1, 0), ("", ""))
                        for g, ev in fields if g == 4]
    return []


def read_ops(path: str) -> list:
    """Per chip, in trace.read_xplane's order: [(start_ns, end_ns, tf_op)]
    of the device's ops, the times as ProfileData reads them."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        planes = {}
        for field, plane in _fields(f.read()):
            if field == 1:
                name = next((v.decode() for g, v in _fields(plane) if g == 2), "")
                if name.startswith(trace.DEVICE_PLANE):
                    planes[name] = plane
    out = []
    for plane in sorted(ProfileData.from_file(path).planes,
                        key=lambda p: p.name):
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        paths = _op_paths(planes[plane.name])
        events = next((list(line.events) for line in plane.lines
                       if line.name == trace.OPS_LINE), [])
        if len(events) != len(paths):
            raise ValueError(f"{plane.name}: {len(events)} ops but "
                             f"{len(paths)} op metadata references")
        ops = []
        for e, (name, tf_op) in zip(events, paths):
            if e.name != name:
                raise ValueError(f"{plane.name}: op {e.name!r} read with "
                                 f"the metadata of {name!r}")
            ops.append((e.start_ns, e.start_ns + e.duration_ns, tf_op))
        out.append(ops)
    return out


def scope_of(tf_op: str, scopes) -> str:
    """The innermost of `scopes` among the components of an op's path
    ("jit(search)/while/body/insert/sort:" is in "insert"), else
    UNSCOPED."""
    for part in reversed(tf_op.rpartition(":")[0].split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def scope_self_ns(ops, scopes, windows) -> dict:
    """Device self nanoseconds per scope (and UNSCOPED) of the ops, clipped
    to `windows`: an op's time less the ops nested in it, as
    trace.self_times counts it, so the values add up to the time the ops
    cover inside the windows."""
    labeled = [(s, e, scope_of(tf_op, scopes))
               for s, e, tf_op in trace.within(ops, windows)]
    return trace.self_times(labeled)


def span_seconds(spans, name: str) -> float:
    """Seconds of the host spans ([(start, end, name)]) called `name`."""
    return sum((e - s) / 1e9 for s, e, n in spans if n == name)


def executions_within(modules, spans) -> int:
    """Device program executions (module events) whose midpoint lies
    inside any of `spans` ([(start, end, ...)])."""
    return sum(1 for s, e, _ in modules
               if any(w[0] <= (s + e) / 2 < w[1] for w in spans))
