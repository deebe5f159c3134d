"""The host span recorder, ``repro.spans``."""
import threading
import time

import pytest

from repro import spans


def test_a_root_records_its_seconds_and_children_summed_by_name():
    with spans.span("t.root"):
        with spans.span("t.a"):
            time.sleep(0.01)
        with spans.span("t.b"):
            pass
        with spans.span("t.a"):
            time.sleep(0.01)
    rec = spans.last("t.root")
    assert rec.name == "t.root"
    assert set(rec.children) == {"t.a", "t.b"}
    assert rec.children["t.a"] >= 0.02
    assert sum(rec.children.values()) <= rec.seconds


def test_grandchildren_are_summed_under_the_root():
    with spans.span("t.root"):
        with spans.span("t.mid"):
            with spans.span("t.leaf"):
                time.sleep(0.005)
    rec = spans.last("t.root")
    assert rec.children["t.leaf"] >= 0.005
    assert rec.children["t.mid"] >= rec.children["t.leaf"]
    assert spans.last("t.mid") is None  # a nested span is no root


@pytest.mark.parametrize("where", ["entry", "body", "child"])
def test_counts_are_summed_by_key_wherever_given(where):
    # n = 2 in all three, given at the root's entry, in its body, or
    # half at a child's entry and half in the child's body.
    entry = {"n": 2} if where == "entry" else {}
    with spans.span("t.counted", **entry) as root:
        if where == "body":
            root.count(n=2)
        child_entry = {"n": 1} if where == "child" else {}
        with spans.span("t.child", **child_entry) as child:
            child.count(bytes=10)
            if where == "child":
                child.count(n=1)
    assert dict(spans.last("t.counted").counts) == {"n": 2, "bytes": 10}


def test_only_the_newest_root_of_a_name_is_kept():
    for n in (1, 2, 3):
        with spans.span("t.newest", call=n):
            pass
    assert spans.last("t.newest").counts["call"] == 3
    assert spans.last("t.never") is None


def test_a_raising_body_closes_its_spans():
    with pytest.raises(RuntimeError):
        with spans.span("t.raises"):
            with spans.span("t.inner"):
                raise RuntimeError("boom")
    rec = spans.last("t.raises")
    assert rec.seconds >= rec.children["t.inner"] >= 0
    # Nothing is left open: the next span is a root of its own.
    with spans.span("t.after"):
        pass
    assert spans.last("t.after").children == {}


def test_memory_does_not_grow_over_calls():
    kept = len(spans._LAST)
    for _ in range(1_000):
        with spans.span("t.loop", runs=1):
            with spans.span("t.loop.child"):
                pass
    rec = spans.last("t.loop")
    assert len(spans._LAST) == kept + 1
    assert dict(rec.counts) == {"runs": 1}
    assert list(rec.children) == ["t.loop.child"]


def test_threads_keep_their_own_roots():
    def work(name):
        with spans.span(name):
            time.sleep(0.01)

    with spans.span("t.main"):
        t = threading.Thread(target=work, args=("t.thread",))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert spans.last("t.thread").seconds >= 0.01
    assert "t.thread" not in spans.last("t.main").children
