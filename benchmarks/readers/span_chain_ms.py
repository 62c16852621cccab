"""Per-transaction time that spans of ONE NAME hold on the blocking chain.

The same walk as ``span_self_time`` (this file runs that file's ``blame``
and ``read``, unedited, from a private copy of the module), but a span is
charged under its own name instead of a component: ``flow.step`` is the
flow code a transaction waited for, ``wait.runnable`` its wait for the
node's thread, and ``flow.run`` what is left on the root (and on every
nested ``flow.run``) once the named children are taken out, i.e. the time
the program's spans do not name. Returns None when no span of that name
exists at all: a program without the span has nothing to read."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_readers_span_self_time_by_name",
    pathlib.Path(__file__).with_name("span_self_time.py"))
_walk = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_walk)
_walk.component_of = lambda span: str(span.get("name", ""))


def read(data, span, q, flow_types=None, needs=None, scale=1000.0):
    """``needs``: a span name that has to occur for the reading to mean
    anything (the un-named remainder of a program without ``flow.step`` is
    all of its flow time, not a remainder)."""
    names = {s.get("name") for s in data.get("spans") or []}
    if span not in names or (needs is not None and needs not in names):
        return None
    return _walk.read(data, component=span, q=q, flow_types=flow_types,
                      scale=scale)
