"""events.Recorder: the dedupe window, the per-reason limiter, retention,
reset — and that a publish costs what expires, not what the dedupe map holds
(recorder.go:44-79)."""

import collections
import sys
import threading

import pytest

from karpenter_core_tpu.events import Event, Recorder, events as evt
from karpenter_core_tpu.events.recorder import DEDUPE_TTL_SECONDS
from karpenter_core_tpu.testing import make_node, make_pod
from karpenter_core_tpu.utils.clock import FakeClock


def event(i: int, reason: str = "Tested", qps=None) -> Event:
    return Event(
        involved_object=None, type="Normal", reason=reason, message=f"event {i}",
        dedupe_values=[str(i)], rate_limit_qps=qps,
    )


@pytest.fixture()
def clock():
    return FakeClock()


@pytest.fixture()
def recorder(clock):
    return Recorder(clock=clock.now)


# seconds between two publishes of one key; how many are published of the two,
# and of three when a third follows the second by just under the TTL
@pytest.mark.parametrize(
    "gap_s, of_two, of_three",
    [(0.0, 1, 1), (1.0, 1, 2), (DEDUPE_TTL_SECONDS - 0.001, 1, 2),
     # published again = stamped again: the window restarts at the second publish
     (DEDUPE_TTL_SECONDS, 2, 2), (DEDUPE_TTL_SECONDS + 30.0, 2, 2)],
)
def test_same_key_is_dropped_within_the_ttl_and_published_after_it(
    recorder, clock, gap_s, of_two, of_three
):
    sunk = []
    recorder.sink = sunk.append
    recorder.publish(event(1))
    clock.step(gap_s)
    recorder.publish(event(1))
    assert len(recorder.events) == len(sunk) == of_two
    clock.step(DEDUPE_TTL_SECONDS - 0.001)
    recorder.publish(event(1))
    assert len(recorder.events) == len(sunk) == of_three
    assert len(recorder._seen) == 1


@pytest.mark.parametrize(
    "make, other",
    [
        # typed events dedupe on their dedupe_values ...
        (lambda pod, node: evt.nominate_pod(pod, node), "node"),
        (lambda pod, node: evt.evict_pod(pod), "pod"),
        # ... an event without them on (type, reason, namespace, name, message)
        (lambda pod, node: Event(pod, "Normal", "Plain", f"on {node.name}"), "node"),
    ],
)
def test_the_key_is_what_dedupes(recorder, make, other):
    pod, node = make_pod(), make_node()
    recorder.publish(make(pod, node))
    recorder.publish(make(pod, node))
    assert len(recorder.events) == 1
    recorder.publish(make(make_pod(), node) if other == "pod" else make(pod, make_node()))
    assert len(recorder.events) == 2


def test_the_limiter_is_per_reason(recorder, clock):
    for i in range(30):
        recorder.publish(event(i, reason="Limited", qps=1.0))
    assert len(recorder.events) == 10  # the bucket's burst
    # a limited event was not stamped: it is not deduped once tokens return
    assert len(recorder._seen) == 10
    for i in range(30):
        recorder.publish(event(100 + i, reason="AlsoLimited", qps=1.0))
    assert [e.reason for e in recorder.events].count("AlsoLimited") == 10
    recorder.publish(event(500, reason="Unlimited"))
    assert len(recorder.events) == 21
    clock.step(3.0)  # 3 tokens at 1 qps
    for i in range(30):
        recorder.publish(event(i, reason="Limited", qps=1.0))  # 0..9 deduped, 10.. limited
    assert [e.reason for e in recorder.events].count("Limited") == 13


def test_retention_is_bounded(recorder, monkeypatch):
    monkeypatch.setattr(Recorder, "MAX_RETAINED_EVENTS", 100)
    for i in range(250):
        recorder.publish(event(i))
    assert [e.message for e in recorder.events] == [f"event {i}" for i in range(150, 250)]
    assert len(recorder._seen) == 250  # retention drops events, not dedupe stamps


def test_reset_forgets_events_and_stamps(recorder):
    recorder.publish(event(1))
    recorder.reset()
    assert recorder.events == [] and len(recorder._seen) == 0
    recorder.publish(event(1))  # not deduped against the forgotten stamp
    assert len(recorder.events) == 1


def test_no_entry_past_its_ttl_is_left_after_a_publish(recorder, clock):
    for i in range(3_000):
        recorder.publish(event(i))
        if i % 1_000 == 999:
            clock.step(DEDUPE_TTL_SECONDS / 3)  # three generations, 40 s apart
    assert len(recorder._seen) == 3_000
    recorder.publish(event(-1))  # the first generation is 120 s old, the second 80
    assert len(recorder._seen) == 2_001
    clock.step(DEDUPE_TTL_SECONDS)
    recorder.publish(event(-2))
    assert list(recorder._seen) == [(event(-2).reason, "-2")]


def test_a_key_published_again_after_its_ttl_moves_to_the_new_end(recorder, clock):
    recorder.publish(event(1))
    clock.step(60.0)
    recorder.publish(event(2))
    clock.step(60.0)
    recorder.publish(event(1))  # past its TTL: out of the front, back at the end
    assert [k[1] for k in recorder._seen] == ["2", "1"]
    clock.step(60.0)
    recorder.publish(event(3))  # expires 2 alone: 1 carries its second stamp
    assert [k[1] for k in recorder._seen] == ["1", "3"]
    recorder.publish(event(1))
    assert len(recorder.events) == 4  # ... and that stamp still dedupes


class _CountingSeen(collections.OrderedDict):
    """The dedupe map, counting the entries a publish walks."""

    walked = 0

    def _count(self, it):
        for x in it:
            type(self).walked += 1
            yield x

    def __iter__(self):
        return self._count(super().__iter__())

    def items(self):
        return self._count(super().items())

    def keys(self):
        return self._count(super().keys())

    def values(self):
        return self._count(super().values())


def test_a_publish_does_not_walk_the_map(clock):
    recorder = Recorder(clock=clock.now)
    recorder._seen = _CountingSeen()
    _CountingSeen.walked = 0
    n = 5_000
    for i in range(n):
        recorder.publish(event(i))
    assert len(recorder._seen) == n
    # a look at the oldest entry a publish — never the map (the sweep this
    # replaced walked 1 024 + ... + 5 000 = 12 M entries here)
    assert _CountingSeen.walked <= n
    # when everything expires at once the cost is what expires
    _CountingSeen.walked = 0
    clock.step(DEDUPE_TTL_SECONDS)
    recorder.publish(event(n))
    assert len(recorder._seen) == 1
    assert _CountingSeen.walked <= n + 1


def test_sixteen_threads_publishing_the_same_keys():
    threads, keys, each = 16, 5_000, 1_250  # 20 000 publishes
    recorder = Recorder()
    pods = [make_pod(name=f"pod-{i}") for i in range(keys)]
    node = make_node()
    errors = []

    def worker(t):
        try:
            for j in range(each):
                # every key is published by four threads
                recorder.publish(evt.nominate_pod(pods[(t * each + j) % keys], node))
        except Exception as e:  # noqa: BLE001 - surfaced by the assertion
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert errors == []
    assert len(recorder.events) == len(recorder._seen) == keys
    assert {e.involved_object.name for e in recorder.events} == {p.name for p in pods}
