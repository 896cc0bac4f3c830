"""``serve.requests_per_group`` on hand-made program spans: the window's
requests over the ``serve.group`` spans that ended in the window."""

import pytest

from bench import run
from bench import spans as sp

OPS = {"/device:TPU:0": [("a", 0.0, 10.0)]}
FOUR = {"requests": [{"start": 0.0, "first": 1.0, "complete": 9.0}] * 4}


def _request_spans(rid, a, b):
    return [sp.Span("serve.request", a, b, 1, {"rid": rid})]


@pytest.mark.parametrize("spans,want", [
    # one group of four members, nested request spans inside it
    ([sp.Span("serve.group", 0.5, 9.0, 1, {"rid": 0})]
     + [s for rid in range(4) for s in _request_spans(rid, 0.5, 9.0)], 4.0),
    # four groups of one
    ([s for rid in range(4) for s in
      [sp.Span("serve.group", 0.5 + 2 * rid, 2.0 + 2 * rid, 1, {"rid": rid}),
       *_request_spans(rid, 0.5 + 2 * rid, 2.0 + 2 * rid)]], 1.0),
    # a program without groups: requests alone
    ([s for rid in range(4) for s in _request_spans(rid, 0.5 + 2 * rid,
                                                    2.0 + 2 * rid)], None),
], ids=["one_group_of_four", "four_groups_of_one", "no_group_span"])
def test_requests_per_group(monkeypatch, spans, want):
    got = sp.reduce(OPS, [sp.Span("bench.window", 0.0, 10.0), *spans])
    monkeypatch.setattr(sp, "of_run", lambda trace: got)
    read = run.metric_reader("serve.requests_per_group")
    assert read(FOUR, {}) == want
    assert read({}, {}) is None


def test_requests_per_group_counts_groups_that_ended_in_the_window(
        monkeypatch):
    # the second group ends after the window: only the first is counted
    spans = [sp.Span("bench.window", 0.0, 10.0),
             sp.Span("serve.group", 0.5, 5.0, 1, {"rid": 0}),
             sp.Span("serve.group", 5.0, 12.0, 1, {"rid": 4})]
    got = sp.reduce(OPS, spans)
    monkeypatch.setattr(sp, "of_run", lambda trace: got)
    assert run.metric_reader("serve.requests_per_group")(FOUR, {}) == 4.0
