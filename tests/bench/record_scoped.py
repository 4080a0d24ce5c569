"""Record the scoped test traces on one chip.

    python tests/bench/record_scoped.py <out_dir>

For each of ``dqn-nature`` and ``rainbow-nature``, builds the tiny cell
of ``tiny_cell.py`` (10x10 frames, a one-conv net, one replica of W=4
envs, C=32, replay 256), drives its first cycles as the harness's
set-up does, and profiles one more cycle inside the harness's
annotations (``bench.harness._profile``). The trace is trimmed to what
the reduction reads (``trim``) and written to
``<out_dir>/scoped_<variant>_tpu.xplane.pb``; ``test_bench_scopes.py``
reads them.
"""

import shutil
import sys
import tempfile
from pathlib import Path

from tiny_cell import CELL, ROOT, make

sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import cells, harness, xplane  # noqa: E402
from bench import trace as tr  # noqa: E402

SEED = 2 ** 32 + 7
CONFIGS = {"dqn": "dqn-nature", "rainbow": "rainbow-nature"}
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
KEPT_STATS = ("tf_op",)        # of an op's metadata, what the readers use


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One field in wire format: an int as a varint, bytes as
    length-delimited. The messages rewritten here (XSpace, XPlane,
    XLine, XEvent, XEventMetadata and map entries) have no fixed-width
    fields; an XStat, which has, is copied whole."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = bytes(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _message(fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def _stat_names(plane) -> dict:
    names = {}
    for number, value in xplane.fields(plane):
        if number == 5:
            entry = dict(xplane.fields(xplane._map_value(value)))
            names[entry.get(1, 0)] = bytes(entry.get(2, b"")).decode()
    return names


def _stat_name(stat, names) -> str:
    return names.get(dict(xplane.fields(stat)).get(1), "")


def _event_names(plane) -> dict:
    out = {}
    for number, value in xplane.fields(plane):
        if number == 4:
            meta = dict(xplane.fields(xplane._map_value(value)))
            out[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
    return out


def _trim_metadata(entry, names) -> bytes:
    key, meta = 0, b""
    for number, value in xplane.fields(entry):
        if number == 1:
            key = value
        elif number == 2:
            meta = _message(
                (n, v) for n, v in xplane.fields(value)
                if n != 5 or _stat_name(v, names) in KEPT_STATS)
    return _message([(1, key), (2, meta)])


def _trim_host_line(line, keep) -> bytes:
    return _message((n, v) for n, v in xplane.fields(line)
                    if n != 4 or keep(v))


def _trim_op_line(line) -> bytes:
    """An op's start and duration are fields of its event; its stats
    repeat them in another unit."""
    return _message(
        (n, _message((n2, v2) for n2, v2 in xplane.fields(v) if n2 != 4)
         if n == 4 else v) for n, v in xplane.fields(line))


def _trim_plane(plane) -> bytes:
    """A device plane keeps its op line without event stats and its
    module line; the host plane keeps the harness's annotations and the
    events that carry a ``run_id`` (the launches the clock offset
    pairs)."""
    name = ""
    for number, value in xplane.fields(plane):
        if number == 2:
            name = bytes(value).decode()
            break
    device = bool(tr.DEVICE_PLANE.match(name))
    names = _stat_names(plane)
    events = _event_names(plane)

    def keep_event(ev):
        fields = list(xplane.fields(ev))
        meta = dict(fields).get(1, 0)
        return (events.get(meta, "").startswith(tr.HOST_PREFIX)
                or any(n == 4 and _stat_name(v, names) == "run_id"
                       for n, v in fields))

    out = []
    for number, value in xplane.fields(plane):
        if number == 3:
            line = dict(xplane.fields(value))
            if device:
                line_name = bytes(line.get(2, b"")).decode()
                if line_name == OP_LINE:
                    out.append((3, _trim_op_line(value)))
                elif line_name == MODULE_LINE:
                    out.append((3, value))
            else:
                trimmed = _trim_host_line(value, keep_event)
                if any(n == 4 for n, _ in xplane.fields(memoryview(trimmed))):
                    out.append((3, trimmed))
        elif number == 4 and device:
            out.append((4, _trim_metadata(value, names)))
        elif number != 6:
            out.append((number, value))
    return _message(out)


def trim(data: bytes) -> bytes:
    """The XSpace with only the chips' planes and the host plane, cut
    to what ``bench/trace.py`` and ``bench/scopes.py`` read."""
    planes = []
    for number, value in xplane.fields(memoryview(data)):
        if number != 1:
            continue
        name = ""
        for n2, v2 in xplane.fields(value):
            if n2 == 2:
                name = bytes(v2).decode()
                break
        if tr.DEVICE_PLANE.match(name) or name == tr.HOST_PLANE:
            planes.append((1, _trim_plane(value)))
    return _message(planes)


def main(out: Path) -> None:
    from repro.api import ExperimentSpec, build_trainer
    harness.device_check(1)
    for variant, config in CONFIGS.items():
        with tempfile.TemporaryDirectory() as tmp:
            cell = cells.load_cell(CELL, make(Path(tmp), config))
            spec = ExperimentSpec.from_dict(cells.spec_dict(cell, SEED))
            trainer = build_trainer(spec)
            carry, _ = harness.first_cycles(trainer)
            carry, trace_dir, path = harness._profile(trainer, carry, 1)
            dest = out / f"scoped_{variant}_tpu.xplane.pb"
            dest.write_bytes(trim(Path(path).read_bytes()))
            shutil.rmtree(trace_dir, ignore_errors=True)
            print(f"{dest}: {dest.stat().st_size} bytes", flush=True)


if __name__ == "__main__":
    main(Path(sys.argv[1]))
