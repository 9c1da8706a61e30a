"""The sharded inference step: one jit over a dp×sp device mesh.

This is the TPU-native generalization of the reference's scaling
mechanics (SURVEY.md §2.3):

* **dp** (data parallel) shards the *video* axis — what the reference
  did with replica processes competing on one queue
  (reference benchmark.py:248-271);
* **sp** (segment parallel) shards the *clip* axis — what the
  reference did with ``num_segments`` row-splitting, forked TimeCards
  and a host-side aggregator summing logits per request
  (reference runner.py:138-173, models/r2p1d/model.py:238-285). Here
  the split, the compute and the merge all live inside one compiled
  program: every ``sp`` member computes logits for its clip shard and a
  ``psum`` over the ``sp`` axis reduces them on-chip over ICI — no host
  round-trip, no queue hop, no aggregator stage.

Variable clip counts (1..max_clips per video) are handled the same way
the rest of the framework handles them: fixed max-shape batches plus a
validity mask (reference control.py:34-39 kept as the shape idiom), so
XLA compiles exactly once.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence, Tuple

import numpy as np

from rnb_tpu.models.r2p1d import checkpoint as ckpt
from rnb_tpu.models.r2p1d.network import (KINETICS_CLASSES, NUM_LAYERS,
                                          R18_LAYER_SIZES,
                                          R2Plus1DClassifier, normalize_u8)


class ShardedInference:
    """Full R(2+1)D inference jitted once over a ``dp × sp`` mesh.

    ``run(videos_u8, clip_mask)`` takes a uint8 batch of shape
    ``(videos, max_clips, frames, H, W, 3)`` and a float mask
    ``(videos, max_clips)`` (1.0 = valid clip) and returns per-video
    aggregated logits ``(videos, num_classes)`` — already summed over
    each video's valid clips and psum-reduced across the ``sp`` axis.

    The mesh's ``dp`` size must divide the video axis. The clip axis
    needs no divisibility: when ``sp`` does not divide ``max_clips`` the
    step pads the clip axis up to the next multiple *inside* the
    compiled program — the padded rows carry a zero mask, so they cost
    one slice of dead MXU work and change no result. That is what lets
    e.g. ``sp=8`` serve ``max_clips=15`` (15 -> 16) and use every core
    of an 8-device mesh instead of idling three (the reference's
    segment parallelism had the same constraint and simply required
    divisibility).
    """

    def __init__(self, mesh, max_clips: int = 15,
                 consecutive_frames: int = 8,
                 frame_hw: int = 112,
                 num_classes: int = KINETICS_CLASSES,
                 layer_sizes: Sequence[int] = R18_LAYER_SIZES,
                 dtype: Any = None,
                 ckpt_path: Optional[str] = None,
                 dp_axis: str = "dp", sp_axis: str = "sp",
                 variables: Optional[Any] = None,
                 factored_shortcut: bool = False,
                 pixel_path: str = "rgb"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if dp_axis not in mesh.axis_names or sp_axis not in mesh.axis_names:
            raise ValueError("mesh %r lacks axis %r/%r"
                             % (mesh.axis_names, dp_axis, sp_axis))
        if pixel_path not in ("rgb", "yuv420"):
            raise ValueError("pixel_path must be 'rgb' or 'yuv420', "
                             "got %r" % (pixel_path,))
        self.mesh = mesh
        self.max_clips = int(max_clips)
        self.consecutive_frames = int(consecutive_frames)
        self.frame_hw = int(frame_hw)
        self.num_classes = int(num_classes)
        self.dp_axis = dp_axis
        self.sp_axis = sp_axis
        self.pixel_path = pixel_path
        dtype = dtype or jnp.bfloat16
        layer_sizes = tuple(layer_sizes)

        sp_size = mesh.shape[sp_axis]
        self.sp_size = sp_size
        #: internal clip-axis extent: max_clips rounded up to a multiple
        #: of sp so every sp member gets an equal shard
        self.padded_clips = -(-self.max_clips // sp_size) * sp_size

        model = R2Plus1DClassifier(start=1, end=NUM_LAYERS,
                                   num_classes=num_classes,
                                   layer_sizes=layer_sizes, dtype=dtype,
                                   factored_shortcut=factored_shortcut)

        if variables is None:
            variables = ckpt.load_or_init(
                1, NUM_LAYERS, num_classes, layer_sizes, ckpt_path,
                factored_shortcut=factored_shortcut)
        replicated = NamedSharding(mesh, P())
        self.variables = jax.device_put(variables, replicated)

        clip_pad = self.padded_clips - self.max_clips
        # External arrays always carry max_clips clip rows. With no
        # padding the clip axis is sharded straight over sp (each core
        # receives only its shard on transfer); with padding the input
        # arrives dp-sharded/sp-replicated and the jitted step pads +
        # slices it — the broadcast is the price of using every core
        # when sp does not divide max_clips.
        if clip_pad == 0:
            self.batch_sharding = NamedSharding(mesh, P(dp_axis, sp_axis))
        else:
            self.batch_sharding = NamedSharding(mesh, P(dp_axis))
        self.logit_sharding = NamedSharding(mesh, P(dp_axis))

        hw = self.frame_hw

        def step(variables, vids, mask):
            # local shapes: vids (v, c, F, H, W, 3) for rgb or
            # (v, c, F, packed) for yuv420; mask (v, c)
            v, c = vids.shape[0], vids.shape[1]
            flat = vids.reshape((v * c,) + vids.shape[2:])
            if pixel_path == "yuv420":
                # the same fused on-device ingest the single-chip
                # network stage runs (rnb_tpu/ops/yuv.py), here inside
                # the sharded program so it shards with the clip axis
                from rnb_tpu.ops.yuv import normalize_yuv420
                with jax.named_scope("ingest"):
                    x = normalize_yuv420(flat, hw, hw, dtype)
            else:
                x = normalize_u8(flat, dtype)
            logits = model.apply(variables, x, train=False)
            logits = logits.reshape(v, c, self.num_classes)
            per_video = (logits * mask[..., None]).sum(axis=1)
            return jax.lax.psum(per_video, sp_axis)

        # check_vma=False: the rgb ingest is a Pallas kernel where this
        # compiles for a TPU, and pallas_call declares no varying-axes
        # type for its output
        sharded = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(dp_axis, sp_axis), P(dp_axis, sp_axis)),
            out_specs=P(dp_axis), check_vma=False)
        if clip_pad == 0:
            self._run = jax.jit(sharded)
        else:
            def padded(variables, vids, mask):
                # rank differs per pixel path — pad only the clip axis
                vids = jnp.pad(
                    vids, ((0, 0), (0, clip_pad))
                    + ((0, 0),) * (vids.ndim - 2))
                mask = jnp.pad(mask, ((0, 0), (0, clip_pad)))
                return sharded(variables, vids, mask)
            self._run = jax.jit(padded)

    def batch_shape(self, num_videos: int) -> Tuple[int, ...]:
        if self.pixel_path == "yuv420":
            from rnb_tpu.ops.yuv import packed_frame_bytes
            return (num_videos, self.max_clips, self.consecutive_frames,
                    packed_frame_bytes(self.frame_hw, self.frame_hw))
        return (num_videos, self.max_clips, self.consecutive_frames,
                self.frame_hw, self.frame_hw, 3)

    def place_mask(self, valid_clips: Sequence[int]):
        """The one clip-validity mask convention: float32 (videos,
        max_clips), 1.0 = valid row, sharded like the batch."""
        import jax
        mask = np.zeros((len(valid_clips), self.max_clips), np.float32)
        for i, n in enumerate(valid_clips):
            mask[i, : int(n)] = 1.0
        return jax.device_put(mask, self.batch_sharding)

    def place(self, videos_u8: np.ndarray, valid_clips: Sequence[int]):
        """Device-put a host batch + derive its mask, both sharded."""
        import jax
        vids = jax.device_put(videos_u8, self.batch_sharding)
        return vids, self.place_mask(valid_clips)

    def run(self, vids, mask):
        """-> per-video aggregated logits (videos, num_classes), fp32."""
        return self._run(self.variables, vids, mask)

    def predict(self, videos_u8: np.ndarray,
                valid_clips: Sequence[int]) -> np.ndarray:
        """Host convenience: class ids for one padded uint8 batch."""
        vids, mask = self.place(videos_u8, valid_clips)
        logits = self.run(vids, mask)
        return np.asarray(logits).argmax(axis=-1)


def make_sharded_inference(mesh=None, num_devices: Optional[int] = None,
                           **kwargs) -> ShardedInference:
    """Build a :class:`ShardedInference` over ``mesh`` (or an
    auto-factored dp×sp mesh over ``num_devices`` / all devices)."""
    if mesh is None:
        import jax
        from rnb_tpu.parallel.mesh import build_mesh
        devices = list(jax.devices())
        if num_devices is not None:
            if num_devices > len(devices):
                raise ValueError(
                    "asked for %d devices but only %d are visible"
                    % (num_devices, len(devices)))
            devices = devices[:num_devices]
        mesh = build_mesh(devices, axis_names=("dp", "sp"))
    return ShardedInference(mesh, **kwargs)
