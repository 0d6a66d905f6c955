"""Snapshot wire codec tests: a full session snapshot tree encoded to one
v2 binary frame (``engine.snapshot.encode_snapshot``) and decoded back must
be bitwise identical; truncated/corrupted/mis-versioned frames must be
rejected loudly — a migration must never restore silently-corrupt state."""

import json

import numpy as np
import pytest

from repro import engine
from repro.core import drift as drift_mod
from repro.core import oselm, pruning
from repro.engine import rpc, snapshot, stream


def _cfg():
    return engine.EngineConfig(
        elm=oselm.OSELMConfig(
            n_in=24, n_hidden=16, n_out=4, variant="hash", ridge=1e-2
        ),
        prune=pruning.PruneConfig(min_trained=1_000_000),
        drift=drift_mod.DriftConfig(),
    )


def _assert_trees_bitwise(a, b, path=""):
    if isinstance(a, (dict, list, tuple)):
        # Container structure must match exactly; leaves are compared as
        # arrays (a python/numpy scalar decodes as its 0-d array, exactly
        # like the np.save/np.load checkpoint path).
        assert type(a) is type(b), f"{path}: {type(a)} != {type(b)}"
    if isinstance(a, dict):
        assert sorted(a) == sorted(b), f"{path}: keys differ"
        for k in a:
            _assert_trees_bitwise(a[k], b[k], f"{path}/{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), f"{path}: length differs"
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_trees_bitwise(x, y, f"{path}[{i}]")
    else:
        xa, xb = np.asarray(a), np.asarray(b)
        assert xa.dtype == xb.dtype, f"{path}: dtype {xa.dtype} != {xb.dtype}"
        assert xa.shape == xb.shape, f"{path}: shape {xa.shape} != {xb.shape}"
        assert xa.tobytes() == xb.tobytes(), f"{path}: bytes differ"


def test_roundtrip_all_leaf_dtypes():
    """Every dtype the snapshot tree actually carries — floats, ints,
    bools, and the 0-d unicode JSON meta leaf — survives bitwise."""
    tree = {
        "meta": np.asarray('{"v": 1, "t": 17}'),  # 0-d <U17
        "f32": np.linspace(-1, 1, 12, dtype=np.float32).reshape(3, 4),
        "f64": np.array([np.pi, -0.0, np.inf], np.float64),
        "i32": np.arange(-3, 3, dtype=np.int32),
        "i64": np.array([2**40, -(2**40)], np.int64),
        "u8": np.arange(256, dtype=np.uint8),
        "bool": np.array([True, False, True]),
        "scalar": np.float32(0.25),
        "nested": {"ring": [np.zeros((2, 2), np.float32),
                            (np.int32(7), np.ones(3, bool))]},
        "empty": np.zeros((0, 4), np.float32),
    }
    out = snapshot.decode_snapshot(snapshot.encode_snapshot(tree))
    _assert_trees_bitwise(tree, out)
    # NaN payloads must survive too (checksums compare bytes, not values).
    nan_tree = {"x": np.array([np.nan, 1.0], np.float32)}
    out = snapshot.decode_snapshot(snapshot.encode_snapshot(nan_tree))
    assert np.isnan(out["x"][0]) and out["x"][1] == 1.0


def test_roundtrip_real_session_snapshot():
    """A live mid-stream session's full snapshot tree (engine state,
    pending ring, teacher RNG, stats) roundtrips bitwise over the wire."""
    cfg = _cfg()
    rng = np.random.default_rng(3)
    teacher = stream.LatencyTeacher(
        label_fn=lambda tick, feats: rng.integers(
            0, 4, size=np.asarray(feats).shape[0]
        ),
        latency=2, jitter=2, loss_prob=0.2, seed=5,
    )
    sess = stream.StreamSession(
        engine.init_fleet(cfg, 4), cfg, teacher, mode="train_phase",
        capacity=4, backpressure="coalesce",
    )
    xs = np.tanh(rng.normal(size=(40, 4, 24))).astype(np.float32)
    sess.start(xs[0])
    for x in xs[1:]:
        sess.advance(x)
    tree = sess.snapshot()
    wire = snapshot.encode_snapshot(tree)
    assert isinstance(wire, bytes) and wire[0] == rpc.WIRE_V2
    _assert_trees_bitwise(tree, snapshot.decode_snapshot(wire))
    meta = json.loads(np.asarray(snapshot.decode_snapshot(wire)["meta"]).item())
    assert meta["stats"]["learns_full"] == sess.stats.learns_full > 0
    assert meta["stats"]["learn_rows"] == sess.stats.learn_rows
    # In-flight ring state really was mid-flight (the interesting case).
    assert len(sess.ring) > 0


def test_truncated_frame_rejected():
    wire = snapshot.encode_snapshot({"a": np.arange(8, dtype=np.float32)})
    for cut in (0, 3, 5, len(wire) // 2, len(wire) - 1):
        with pytest.raises(EOFError):
            snapshot.decode_snapshot(wire[:cut])


def test_corrupt_payload_rejected_by_checksum():
    wire = bytearray(
        snapshot.encode_snapshot({"w": np.ones((4, 4), np.float32)})
    )
    wire[-1] ^= 0xFF  # flip one payload byte
    with pytest.raises(ValueError, match="checksum"):
        snapshot.decode_snapshot(bytes(wire))
    # The error names the leaf so the operator knows what rotted.
    with pytest.raises(ValueError, match="'w'"):
        snapshot.decode_snapshot(bytes(wire))


def test_corrupt_header_rejected():
    wire = bytearray(snapshot.encode_snapshot({"a": np.zeros(2, np.float32)}))
    wire[7] ^= 0xFF  # inside the JSON header
    with pytest.raises((ValueError, EOFError)):
        snapshot.decode_snapshot(bytes(wire))


def test_version_byte_mismatch_rejected():
    wire = bytearray(snapshot.encode_snapshot({"a": np.zeros(2, np.float32)}))
    wire[0] = 0x01  # v1 frame byte on a snapshot frame
    with pytest.raises(ValueError, match="version byte"):
        snapshot.decode_snapshot(bytes(wire))


def test_wrong_frame_kind_rejected():
    """A well-formed v2 frame that is not a snapshot (e.g. an RPC teacher
    frame) must be refused, not misparsed."""
    frame = rpc._encode_frame({"kind": "ask", "payload_len": 4}, b"\0\0\0\0")
    with pytest.raises(ValueError):
        snapshot.decode_snapshot(frame)
