"""PaddedBatch, Batcher fusion, selectors and dynamic class loading."""

import numpy as np
import pytest

from rnb_tpu.batcher import Batcher
from rnb_tpu.selector import RoundRobinSelector
from rnb_tpu.stage import PaddedBatch
from rnb_tpu.telemetry import TimeCard, TimeCardList
from rnb_tpu.utils.class_utils import load_class


def test_padded_batch_pads_and_slices():
    rows = np.arange(6, dtype=np.float32).reshape(2, 3)
    pb = PaddedBatch.from_rows(rows, max_rows=5)
    assert pb.data.shape == (5, 3)
    assert pb.valid == 2
    assert pb.max_rows == 5
    np.testing.assert_array_equal(pb.valid_data(), rows)
    np.testing.assert_array_equal(pb.data[2:], np.zeros((3, 3), np.float32))


def test_padded_batch_exact_fit_and_overflow():
    rows = np.ones((4, 2), np.float32)
    pb = PaddedBatch.from_rows(rows, max_rows=4)
    assert pb.valid == 4
    with pytest.raises(ValueError):
        PaddedBatch.from_rows(rows, max_rows=3)


def _clip_batch(n_clips, fill):
    data = np.full((n_clips, 3, 8, 112, 112), fill, dtype=np.float32)
    return (PaddedBatch.from_rows(data, max_rows=15),)


def test_batcher_accumulates_then_fuses():
    b = Batcher(device=None, batch=3)
    out = b(_clip_batch(1, 1.0), None, TimeCard(0))
    assert out == (None, None, None)
    out = b(_clip_batch(2, 2.0), None, TimeCard(1))
    assert out == (None, None, None)
    tensors, non_tensors, card = b(_clip_batch(1, 3.0), "meta-2", TimeCard(2))
    assert non_tensors is None  # fused metadata is unattributable
    assert isinstance(card, TimeCardList)
    assert len(card) == 3
    fused = tensors[0]
    assert fused.valid == 4
    assert fused.data.shape == (15, 3, 8, 112, 112)
    np.testing.assert_array_equal(
        fused.valid_data()[:, 0, 0, 0, 0], [1.0, 2.0, 2.0, 3.0])
    # internal state resets for the next fused batch
    assert b(_clip_batch(1, 9.0), None, TimeCard(3)) == (None, None, None)


def test_batcher_passthrough_when_batch_leq_one():
    b = Batcher(device=None, batch=1)
    tensors = _clip_batch(2, 5.0)
    tc = TimeCard(0)
    out = b(tensors, "meta", tc)
    assert out == (tensors, "meta", tc)


def test_batcher_emits_early_when_request_would_overflow():
    # 8+8 > 15: the second request closes the window early — the
    # pending batch is emitted and the new request starts the next one
    # (one mid-sized video must not abort the run)
    b = Batcher(device=None, batch=2)
    assert b(_clip_batch(8, 1.0), None, TimeCard(0)) == (None, None, None)
    tensors, _, card = b(_clip_batch(8, 2.0), None, TimeCard(1))
    assert tensors[0].valid == 8
    assert len(card) == 1
    np.testing.assert_array_equal(
        tensors[0].valid_data()[:, 0, 0, 0, 0], [1.0] * 8)
    # the displaced request is pending; a follow-up completes its batch
    tensors, _, card = b(_clip_batch(2, 3.0), None, TimeCard(2))
    assert tensors[0].valid == 10
    assert len(card) == 2


def test_batcher_input_shape_follows_constructor_args():
    # regression: input_shape() used to hardcode the flagship
    # (MAX_ROWS, 8, 112, 112, 3) shape regardless of the
    # shapes/max_rows/consecutive_frames/frame_hw the instance was
    # built with, so declared-vs-actual payload validation was wrong
    # for every non-flagship topology
    b = Batcher(device=None, batch=2, max_rows=4, consecutive_frames=2,
                frame_hw=16)
    assert b.input_shape() == ((4, 2, 16, 16, 3),)
    assert b.input_shape() == b.output_shape_for(
        max_rows=4, consecutive_frames=2, frame_hw=16)
    b = Batcher(device=None, batch=2, shapes=[[6, 3], [6, 5]])
    assert b.input_shape() == ((6, 3), (6, 5))
    # default construction keeps the flagship shape
    assert Batcher(device=None, batch=2).input_shape() == \
        ((15, 8, 112, 112, 3),)


def test_batcher_early_emission_non_flagship_window():
    # regression (previously untested): a MID-SIZED request closing a
    # pending window on a non-flagship declared shape — the pending
    # batch must emit with only its own cards and the displaced
    # request must seed the next window intact
    b = Batcher(device=None, batch=3, shapes=[[4, 2]])

    def req(rows, fill):
        return (PaddedBatch.from_rows(
            np.full((rows, 2), fill, dtype=np.float32), max_rows=4),)

    assert b(req(2, 1.0), None, TimeCard(0)) == (None, None, None)
    # 2 pending + 3 incoming > 4 declared: early emission fires
    tensors, non_tensors, card = b(req(3, 2.0), None, TimeCard(1))
    assert non_tensors is None
    assert isinstance(card, TimeCardList) and len(card) == 1
    assert tensors[0].valid == 2
    assert tensors[0].data.shape == (4, 2)
    np.testing.assert_array_equal(tensors[0].valid_data()[:, 0],
                                  [1.0, 1.0])
    # the displaced mid-sized request is the next window's seed
    flushed = b.flush()
    assert flushed is not None
    assert flushed[0][0].valid == 3
    assert len(flushed[2]) == 1
    np.testing.assert_array_equal(flushed[0][0].valid_data()[:, 0],
                                  [2.0, 2.0, 2.0])


def test_batcher_rejects_single_oversized_request():
    # a lone request beyond the DECLARED capacity is a topology error
    b = Batcher(device=None, batch=2, shapes=[[4, 3, 8, 112, 112]])
    with pytest.raises(ValueError):
        b(_clip_batch(8, 1.0), None, TimeCard(0))
    # fail-fast left the accumulator intact
    assert b(_clip_batch(2, 2.0), None, TimeCard(1)) == (None, None, None)
    tensors, _, card = b(_clip_batch(2, 3.0), None, TimeCard(2))
    assert tensors[0].valid == 4


def test_round_robin_selector_cycles():
    s = RoundRobinSelector(3)
    picks = [s.select(None, None, None) for _ in range(7)]
    assert picks == [0, 1, 2, 0, 1, 2, 0]


def test_load_class_roundtrip():
    cls = load_class("rnb_tpu.selector.RoundRobinSelector")
    assert cls is RoundRobinSelector
    with pytest.raises(ValueError):
        load_class("NoDots")
    with pytest.raises(ImportError):
        load_class("rnb_tpu.selector.DoesNotExist")


def test_validate_payload_contract():
    import numpy as np
    import pytest
    from rnb_tpu.runner import validate_payload
    from rnb_tpu.stage import PaddedBatch

    declared = ((4, 2),)
    ok = (PaddedBatch(np.zeros((4, 2), np.float32), 3),)
    validate_payload(declared, ok, "step")
    # smaller row axis is legal (row bucketing)
    validate_payload(declared, (PaddedBatch(np.zeros((2, 2)), 1),), "step")
    # trailing-dim mismatch: the exact rot the NCFHW batcher declaration
    # had in round 1 — must be caught, not silently parked
    with pytest.raises(ValueError):
        validate_payload(declared, (PaddedBatch(np.zeros((4, 3)), 1),),
                         "step")
    # larger row axis than declared
    with pytest.raises(ValueError):
        validate_payload(declared, (PaddedBatch(np.zeros((5, 2)), 1),),
                         "step")
    # tensor-count mismatch
    with pytest.raises(ValueError):
        validate_payload(declared, ok * 2, "step")
    # None declaration forbids tensor output; empty payload is fine
    validate_payload(None, None, "step")
    validate_payload(None, (), "step")
    with pytest.raises(ValueError):
        validate_payload(None, ok, "step")


def test_batcher_row_buckets_pad_to_bucket():
    b = Batcher(device=None, batch=3, row_buckets=[4, 15])
    b(_clip_batch(1, 1.0), None, TimeCard(0))
    b(_clip_batch(1, 2.0), None, TimeCard(1))
    tensors, _, card = b(_clip_batch(1, 3.0), None, TimeCard(2))
    # 3 valid rows pad to the 4 bucket, not the 15 max shape
    assert tensors[0].valid == 3
    assert tensors[0].data.shape[0] == 4
    # an oversized fuse still pads to the max shape
    b2 = Batcher(device=None, batch=2, row_buckets=[4, 15])
    b2(_clip_batch(4, 1.0), None, TimeCard(0))
    tensors, _, _ = b2(_clip_batch(4, 2.0), None, TimeCard(1))
    assert tensors[0].data.shape[0] == 15


def test_batcher_flush_emits_partial_batch():
    b = Batcher(device=None, batch=4, row_buckets=[4, 15])
    assert b.flush() is None  # nothing pending
    b(_clip_batch(1, 1.0), None, TimeCard(0))
    b(_clip_batch(1, 2.0), None, TimeCard(1))
    tensors, non_tensors, card = b.flush()
    assert len(card) == 2
    assert tensors[0].valid == 2
    assert tensors[0].data.shape[0] == 4
    np.testing.assert_array_equal(
        tensors[0].valid_data()[:, 0, 0, 0, 0], [1.0, 2.0])
    assert b.flush() is None  # state reset


def test_batcher_fuses_on_device_without_host_bounce():
    """Device-array constituents fuse into a device array on the same
    device — the fused batch must not round-trip through the host
    (that bounce costs a device-to-host transfer per request)."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[1]
    b = Batcher(device=None, batch=2, row_buckets=[4, 15])

    def dev_batch(n, fill):
        data = jnp.full((n, 3, 8, 16, 16), fill, jnp.bfloat16)
        data = jax.device_put(
            jnp.concatenate([data, jnp.zeros((15 - n,) + data.shape[1:],
                                             data.dtype)]), dev)
        return (PaddedBatch(data, n),)

    b(dev_batch(1, 1.0), None, TimeCard(0))
    tensors, _, card = b(dev_batch(2, 2.0), None, TimeCard(1))
    fused = tensors[0]
    assert isinstance(fused.data, jax.Array)
    assert fused.data.devices() == {dev}
    assert fused.valid == 3
    assert fused.data.shape[0] == 4  # padded to the bucket on device
    got = np.asarray(fused.data[:, 0, 0, 0, 0], np.float32)
    np.testing.assert_array_equal(got, [1.0, 2.0, 2.0, 0.0])
