"""Host spans and counters of the round loop (``repro.common.spans``) and
the named scopes of the round program."""
import contextlib
import re

import jax
import pytest

from repro.analysis import hostsync as hs
from repro.analysis.report import Report
from repro.common import spans
from repro.core import CurriculumHP, make_adapter
from repro.data import Batcher, dirichlet_partition, make_image_dataset
from repro.federated.runtime import make_round_program
from repro.federated.server import FLConfig, NeuLiteServer
from repro.models.cnn import CNNConfig
from repro.optim import sgd


# --------------------------------------------------------------------------- #
# the recorder
# --------------------------------------------------------------------------- #
def test_off_records_nothing_and_reads_no_clock(monkeypatch):
    def no_clock():
        raise AssertionError("the clock was read with recording off")

    monkeypatch.setattr(spans.time, "time_ns", no_clock)
    first = spans.span("fl.round", round=0)
    with first as s:
        assert s is None
        with spans.span("fl.open"):
            spans.count("stack_bytes", 10)
    assert spans.span("fl.stack") is first       # one shared null context
    assert spans._active is None


def test_nesting_gives_parents_and_round_ids():
    with spans.recording() as rec:
        with spans.span("outside"):
            pass
        for r in (3, 4):
            with spans.span("fl.round", round=r, stage=1):
                with spans.span("fl.open") as s:
                    s.attrs["n_selected"] = 10
                with spans.span("fl.to_device"):
                    with spans.span("inner"):
                        pass
    got = [(s.name, s.parent, s.round) for s in rec.spans]
    assert got == [("outside", None, None),
                   ("fl.round", None, 3), ("fl.open", 1, 3),
                   ("fl.to_device", 1, 3), ("inner", 3, 3),
                   ("fl.round", None, 4), ("fl.open", 5, 4),
                   ("fl.to_device", 5, 4), ("inner", 7, 4)]
    assert rec.spans[1].attrs == {"round": 3, "stage": 1}
    assert rec.spans[2].attrs == {"n_selected": 10}
    for s in rec.spans:
        assert 0 < s.start_ns <= s.end_ns
        if s.parent is not None:
            p = rec.spans[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns


def test_counters_are_keyed_by_name_and_round():
    with spans.recording() as rec:
        spans.count("h2d_bytes", 5)
        for r in (0, 1):
            with spans.span("fl.round", round=r):
                with spans.span("fl.stack"):
                    spans.count("stack_bytes", 100 + r)
                    spans.count("stack_bytes", 1)
    assert rec.counters == {("h2d_bytes", None): 5,
                            ("stack_bytes", 0): 101,
                            ("stack_bytes", 1): 102}


def test_recording_leaves_the_recorder_clean():
    with pytest.raises(ValueError):
        with spans.recording() as rec:
            with spans.span("fl.round", round=0):
                with pytest.raises(RuntimeError):
                    with spans.recording():
                        pass
                raise ValueError("a round that fails")
    assert rec.open == [] and rec.spans[0].end_ns >= rec.spans[0].start_ns
    assert spans._active is None
    assert spans.span("fl.round") is spans.span("fl.open")
    with spans.recording() as again:
        with spans.span("fl.round", round=1):
            pass
    assert [s.name for s in again.spans] == ["fl.round"]
    assert len(rec.spans) == 1


# --------------------------------------------------------------------------- #
# spans of a toy server round
# --------------------------------------------------------------------------- #
CCFG = CNNConfig(name="r18", arch="resnet18", num_classes=4, image_size=8,
                 width_mult=0.125)
ROUND_SPANS = ["fl.round", "fl.open", "fl.stack", "fl.split",
               "fl.to_device", "fl.dispatch", "fl.merge", "fl.sync"]


def _server(tmp_path=None, test_batcher=False, every=0):
    ds = make_image_dataset(0, 160, num_classes=4, image_size=8)
    flc = FLConfig(n_devices=4, clients_per_round=2, local_epochs=1,
                   batch_size=16, num_stages=2, seed=0, runtime="vectorized",
                   checkpoint_dir=None if tmp_path is None else str(tmp_path),
                   checkpoint_every=every)
    parts = dirichlet_partition(0, ds.labels, 4, alpha=1.0)
    test = (Batcher(make_image_dataset(3, 64, num_classes=4, image_size=8),
                    16, seed=11, kind="image") if test_batcher else None)
    return NeuLiteServer(make_adapter(CCFG, flc.num_stages),
                         [ds.subset(p) for p in parts], flc,
                         test_batcher=test)


def test_vectorized_round_spans_in_order_under_one_round():
    server = _server()
    with spans.recording() as first:            # compile outside the record
        server.run_round(0)
    with spans.recording() as rec:
        rr = server.run_round(2)                # stage 0 again, as round 0
    assert rr.n_selected > 0
    assert [s.name for s in rec.spans] == ROUND_SPANS
    top = rec.spans[0]
    assert top.attrs == {"round": 2, "stage": rr.stage}
    assert all(s.parent == 0 and s.round == 2 for s in rec.spans[1:])
    ends = [(s.start_ns, s.end_ns) for s in rec.spans[1:]]
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
    opening, stacking = rec.spans[1], rec.spans[2]
    assert opening.attrs == {"n_selected": rr.n_selected}
    C, E = stacking.attrs["C"], stacking.attrs["E"]
    assert C == rr.n_selected
    # images (C, E, 16, 8, 8, 3) float32, labels int32, mask, weights
    batch_bytes = C * E * 16 * (8 * 8 * 3 * 4 + 4)
    nbytes = batch_bytes + C * E + C * 4
    # round 0 sized the runtime's stack buffer; round 2 refills or grows it
    had = first.counters[("stack_buffer_grown_bytes", 0)]
    buffer = ({("stack_buffer_reused", 2): 1} if batch_bytes <= had else
              {("stack_buffer_grown_bytes", 2): batch_bytes - had})
    assert rec.counters == {("stack_bytes", 2): nbytes,
                            ("h2d_bytes", 2): nbytes, **buffer}


def test_evaluate_and_checkpoint_spans(tmp_path):
    server = _server(tmp_path, test_batcher=True, every=1)
    with spans.recording() as rec:
        server.run(1)
    names = [s.name for s in rec.spans]
    assert names == ROUND_SPANS + ["fl.evaluate", "fl.checkpoint"]
    assert rec.spans[-2].parent == 0
    assert rec.spans[-1].parent is None and rec.spans[-1].round is None


@pytest.mark.parametrize("record", [False, True])
def test_transfer_probe_passes_with_and_without_recording(record):
    server = _server(test_batcher=True)
    server.run_round(0)
    report = Report()
    with spans.recording() if record else spans._NULL:
        hs.audit_runtime_round(server.runtime, server.params, 0,
                               server.batchers, [0, 1], 1, report)
        hs.audit_server_round(server, report)
    assert report.errors == [], report.findings


# --------------------------------------------------------------------------- #
# the named scopes change metadata only
# --------------------------------------------------------------------------- #
def _stripped(text: str) -> str:
    """Compiled HLO without metadata and without the stack-frame tables
    that only metadata refers to."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    keep = []
    table = False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = True
            continue
        if table and (not line or line[0].isdigit()):
            continue
        table = False
        keep.append(line)
    return "\n".join(keep)


@pytest.mark.parametrize("family", ["cnn", "tx"])
def test_named_scopes_change_only_metadata(family, cnn_setup, tx_setup,
                                           monkeypatch):
    from repro.data.loader import stack_round
    from repro.federated.runtime import abstract_like
    adapter, params, batchers = cnn_setup if family == "cnn" else tx_setup
    t = adapter.plan.num_stages - 1
    stack = stack_round(batchers, local_steps=2)
    frozen, trainable = adapter.split_stage(params, t)
    args = abstract_like((trainable, frozen, stack.batches, stack.weights,
                          stack.step_mask))

    def compiled():
        program = make_round_program(adapter, sgd(0.05, 0.9, 5e-4),
                                     CurriculumHP(mu=0.01), t)
        return jax.jit(program).lower(*args).compile().as_text()

    scoped = compiled()
    for scope in ("prefix", "block", "mentor", "optimizer", "eq1"):
        assert f"neulite.{scope}" in scoped, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "neulite." not in plain
    assert "metadata=" in scoped
    assert _stripped(scoped) == _stripped(plain)
