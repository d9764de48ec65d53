"""Vision models for the in-process TPU server.

TPU-first design notes: forward passes are jitted once with static shapes so
XLA tiles the convolutions onto the MXU; parameters live on device in bfloat16
(compute) with float32 I/O at the protocol boundary. The CNN here is the
hermetic stand-in for the reference's densenet_onnx / inception example models
(BASELINE.md configs 1-2) — same tensor interface (NCHW image in, class scores
out), sized so a single v5e chip turns requests around in sub-millisecond time.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from client_tpu.serve.model_runtime import Model, TensorSpec

# ImageNet-ish class count so classification extension demos look real.
_NUM_CLASSES = 1000


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )


def _init_cnn_params(key, channels=(32, 64, 128, 256), in_ch=3, num_classes=_NUM_CLASSES):
    params = {"convs": [], "scales": []}
    k = key
    prev = in_ch
    for ch in channels:
        k, sub = jax.random.split(k)
        # python-float scale: numpy scalars are not weak-typed and would
        # promote the bfloat16 weights to float32
        params["convs"].append(
            jax.random.normal(sub, (ch, prev, 3, 3), jnp.bfloat16)
            * float(2.0 / np.sqrt(prev * 9))
        )
        params["scales"].append(jnp.ones((ch, 1, 1), jnp.bfloat16))
        prev = ch
    k, sub = jax.random.split(k)
    params["head"] = jax.random.normal(
        sub, (prev, num_classes), jnp.bfloat16
    ) * float(1.0 / np.sqrt(prev))
    return params


def _cnn_forward(params, x):
    # x: [N, 3, H, W] float32 -> scores [N, num_classes] float32
    h = x.astype(jnp.bfloat16)
    for w, s in zip(params["convs"], params["scales"]):
        h = _conv(h, w, stride=2)
        h = jax.nn.relu(h) * s
    h = jnp.mean(h, axis=(2, 3))  # global average pool
    return (h @ params["head"]).astype(jnp.float32)


def _conv_flops(out_ch, in_ch, kh, kw, out_h, out_w):
    # one MAC = 2 FLOPs; elementwise (relu/scale/add) is noise next to this
    return 2 * out_ch * in_ch * kh * kw * out_h * out_w


def cnn_flops_per_image(image_size=224, channels=(32, 64, 128, 256),
                        in_ch=3, num_classes=_NUM_CLASSES):
    """Analytic forward FLOPs for one image through the small CNN."""
    flops, hw, prev = 0, image_size, in_ch
    for ch in channels:
        hw = (hw + 1) // 2  # stride-2 SAME conv
        flops += _conv_flops(ch, prev, 3, 3, hw, hw)
        prev = ch
    return flops + 2 * prev * num_classes


class CnnClassifier:
    """Jitted CNN classifier servable; accepts any batch of 224x224 RGB."""

    def __init__(self, image_size=224, seed=0):
        self.image_size = image_size
        self.params = _init_cnn_params(jax.random.PRNGKey(seed))
        self._forward = jax.jit(_cnn_forward)

    def __call__(self, inputs, params, ctx):
        # jnp.asarray is a no-op for device-resident (TPU-shm) inputs; the
        # output stays a device array so shm-output responses never force a
        # D2H sync — the runtime materializes only for wire-tensor responses.
        x = jnp.asarray(inputs["INPUT0"])
        return {"OUTPUT0": self._forward(self.params, x)}


def cnn_classifier_model(
    name="cnn_classifier", image_size=224, max_batch_size=64, warmup=False
):
    """Servable Model wrapping CnnClassifier (densenet_onnx stand-in).

    Dynamic batching is on: concurrent wire requests fuse into one padded
    batched forward (one H2D, one MXU pass, one D2H per batch).
    """
    runner = CnnClassifier(image_size)
    labels = [f"class_{i}" for i in range(_NUM_CLASSES)]
    return Model(
        name,
        inputs=[TensorSpec("INPUT0", "FP32", [-1, 3, image_size, image_size])],
        outputs=[TensorSpec("OUTPUT0", "FP32", [-1, _NUM_CLASSES], labels=labels)],
        fn=runner,
        platform="jax",
        backend="jax",
        max_batch_size=max_batch_size,
        dynamic_batching=True,
        warmup=warmup,
        batch_device_inputs=True,
        fused_batching=True,
        max_fused_arity=16,
        flops_per_item=cnn_flops_per_image(image_size),
    )


# ---------------------------------------------------------------------------
# ResNet-50 (BASELINE.md config 3: perf_analyzer concurrency sweep on
# resnet50 with TPU HBM input tensors).  Real bottleneck residual blocks at
# the standard [3,4,6,3] depth — 4.09 GMACs = ~8.2 GFLOP per 224x224 image
# (the commonly cited "4.1 GFLOPs" counts MACs), so a
# throughput number on this model is a *compute* statement (MFU), not a
# protocol statement.  Inference-only: batch norm folds into the per-channel
# scales (s1..s3, stem_scale) at serving time.
# ---------------------------------------------------------------------------

# Single source of stage geometry: (mid_channels, n_blocks, first_stride)
# per stage.  _init_resnet_params, _resnet_forward and
# resnet50_flops_per_image all derive from this — change it in one place.
_RESNET50_STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.bfloat16) * float(
        np.sqrt(2.0 / fan_in)
    )


def _init_resnet_params(key, in_ch=3, num_classes=_NUM_CLASSES,
                        stages=_RESNET50_STAGES):
    """Bottleneck ResNet-50 parameters: stem 7x7/2 + maxpool, then stages of
    (mid_ch, n_blocks, first_stride) bottlenecks (1x1 -> 3x3 -> 1x1 with a
    4x expansion), ending in a 1000-way linear head."""
    keys = iter(jax.random.split(key, 256))
    params = {
        "stem": _he(next(keys), (64, in_ch, 7, 7), in_ch * 49),
        "stem_scale": jnp.ones((64, 1, 1), jnp.bfloat16),
        "stages": [],
    }
    prev = 64
    for mid, n_blocks, first_stride in stages:
        out = mid * 4
        blocks = []
        for b in range(n_blocks):
            stride = first_stride if b == 0 else 1
            block = {
                "w1": _he(next(keys), (mid, prev, 1, 1), prev),
                "s1": jnp.ones((mid, 1, 1), jnp.bfloat16),
                "w2": _he(next(keys), (mid, mid, 3, 3), mid * 9),
                "s2": jnp.ones((mid, 1, 1), jnp.bfloat16),
                "w3": _he(next(keys), (out, mid, 1, 1), mid),
                "s3": jnp.ones((out, 1, 1), jnp.bfloat16),
            }
            if prev != out or stride != 1:
                block["proj"] = _he(next(keys), (out, prev, 1, 1), prev)
            blocks.append(block)
            prev = out
        params["stages"].append(blocks)
    params["head_w"] = _he(next(keys), (prev, num_classes), prev)
    params["head_b"] = jnp.zeros((num_classes,), jnp.bfloat16)
    return params


def _bottleneck(block, x, stride):
    h = jax.nn.relu(_conv(x, block["w1"]) * block["s1"])
    h = jax.nn.relu(_conv(h, block["w2"], stride=stride) * block["s2"])
    h = _conv(h, block["w3"]) * block["s3"]
    skip = x if "proj" not in block else _conv(x, block["proj"], stride=stride)
    return jax.nn.relu(h + skip)


def _resnet_features(params, x, stage_strides=None):
    """Backbone half: image -> pooled feature vector (the head applies in
    _resnet_head).  Split out so the vision *pipeline* can serve the
    backbone and the classification head as separate composing models with
    the feature tensor staying device-resident between them."""
    # strides are structural (static under jit tracing), not pytree leaves —
    # conv window_strides must be concrete.  Custom-`stages` params need a
    # matching stage_strides; the default follows _RESNET50_STAGES.
    strides = stage_strides or tuple(s for _, _, s in _RESNET50_STAGES)
    # x: [N, 3, H, W] float32 -> features [N, C] bfloat16
    h = x.astype(jnp.bfloat16)
    h = jax.nn.relu(_conv(h, params["stem"], stride=2) * params["stem_scale"])
    h = jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max,
        window_dimensions=(1, 1, 3, 3),
        window_strides=(1, 1, 2, 2),
        padding="SAME",
    )
    for si, blocks in enumerate(params["stages"]):
        for bi, block in enumerate(blocks):
            h = _bottleneck(block, h, strides[si] if bi == 0 else 1)
    return jnp.mean(h, axis=(2, 3))


def _resnet_head(params, h):
    """Classification head over pooled features -> float32 scores."""
    return (
        h.astype(jnp.bfloat16) @ params["head_w"] + params["head_b"]
    ).astype(jnp.float32)


def _resnet_forward(params, x, stage_strides=None):
    # x: [N, 3, H, W] float32 -> scores [N, num_classes] float32
    return _resnet_head(
        params, _resnet_features(params, x, stage_strides=stage_strides)
    )


def resnet50_flops_per_image(image_size=224, in_ch=3,
                             num_classes=_NUM_CLASSES,
                             stages=_RESNET50_STAGES):
    """Analytic forward FLOPs for one image, 2*MAC convention (convs +
    head): ~8.18e9 for 224px — i.e. 4.09 GMACs, matching torchvision's
    resnet50 profile.  MFU divides this by a peak quoted in FLOP/s, so the
    2*MAC convention is the consistent numerator."""
    def conv_out(hw, stride):
        return (hw + stride - 1) // stride

    flops = 0
    hw = conv_out(image_size, 2)  # stem 7x7/2
    flops += _conv_flops(64, in_ch, 7, 7, hw, hw)
    hw = conv_out(hw, 2)  # maxpool/2
    prev = 64
    for mid, n_blocks, first_stride in stages:
        out = mid * 4
        for b in range(n_blocks):
            stride = first_stride if b == 0 else 1
            # 1x1 reduce runs at the INPUT resolution, the 3x3 at the output
            flops += _conv_flops(mid, prev, 1, 1, hw, hw)
            hw_out = conv_out(hw, stride)
            flops += _conv_flops(mid, mid, 3, 3, hw_out, hw_out)
            flops += _conv_flops(out, mid, 1, 1, hw_out, hw_out)
            if prev != out or stride != 1:
                flops += _conv_flops(out, prev, 1, 1, hw_out, hw_out)
            prev = out
            hw = hw_out
    return flops + 2 * prev * num_classes


class ResNet50Classifier:
    """Jitted bottleneck ResNet-50 servable (~8.2 GFLOP / 224px image)."""

    def __init__(self, image_size=224, seed=0):
        self.image_size = image_size
        self.params = _init_resnet_params(jax.random.PRNGKey(seed))
        self._forward = jax.jit(_resnet_forward)

    def __call__(self, inputs, params, ctx):
        x = jnp.asarray(inputs["INPUT0"])
        return {"OUTPUT0": self._forward(self.params, x)}


# ---------------------------------------------------------------------------
# Vision pipeline (ensemble acceptance workload, serve/pipeline.py):
# preprocess -> resnet backbone -> classification postprocess, all jax-backed
# so every intermediate tensor stays in device HBM between steps — the DAG
# scheduler hands the jax.Array straight to the next composing model with
# zero host round-trips (asserted via ctpu_ensemble_host_hops_total).
# ---------------------------------------------------------------------------

# Tiny stage geometry for the hermetic default-model variant: ~0.4M params,
# compiles in well under a second on CPU.  Full-size callers pass
# stages=_RESNET50_STAGES.
_TINY_STAGES = ((16, 1, 1), (32, 1, 2))

_IMAGENET_MEAN = (0.485, 0.456, 0.406)
_IMAGENET_STD = (0.229, 0.224, 0.225)


def _preprocess_forward(x):
    """uint8 NHWC image batch -> normalized float32 NCHW pixels."""
    x = x.astype(jnp.float32) / 255.0
    x = jnp.transpose(x, (0, 3, 1, 2))
    mean = jnp.asarray(_IMAGENET_MEAN, jnp.float32).reshape(1, 3, 1, 1)
    std = jnp.asarray(_IMAGENET_STD, jnp.float32).reshape(1, 3, 1, 1)
    return (x - mean) / std


class _VisionPipelineRunners:
    """Shared lazy state behind the pipeline's composing models: one resnet
    parameter tree (backbone stages + classification head) initialized on
    first use so constructing the default model set stays cheap."""

    def __init__(self, image_size, stages, num_classes, seed=0):
        self.image_size = image_size
        self.stages = tuple(stages)
        self.num_classes = num_classes
        self.seed = seed
        self.feature_dim = self.stages[-1][0] * 4
        self._params = None  # init is idempotent; racing first calls agree
        self._pre = jax.jit(_preprocess_forward)
        strides = tuple(s for _, _, s in self.stages)
        self._features = jax.jit(
            functools.partial(_resnet_features, stage_strides=strides)
        )
        self._head = jax.jit(_resnet_head)

    def _ensure(self):
        params = self._params
        if params is None:
            params = _init_resnet_params(
                jax.random.PRNGKey(self.seed),
                num_classes=self.num_classes,
                stages=self.stages,
            )
            self._params = params
        return params

    def preprocess(self, inputs, params, ctx):
        return {"PIXELS": self._pre(jnp.asarray(inputs["IMAGE"]))}

    def backbone(self, inputs, params, ctx):
        # jnp.asarray is a no-op for the device-resident PIXELS handoff;
        # the float32 cast honors the FEATURES spec and stays on device
        return {
            "FEATURES": self._features(
                self._ensure(), jnp.asarray(inputs["PIXELS"])
            ).astype(jnp.float32)
        }

    def postprocess(self, inputs, params, ctx):
        scores = self._head(self._ensure(), jnp.asarray(inputs["FEATURES"]))
        return {"SCORES": jax.nn.softmax(scores, axis=-1)}


def vision_pipeline_models(
    image_size=32,
    stages=_TINY_STAGES,
    num_classes=16,
    max_batch_size=32,
    warmup=False,
    prefix="vision",
):
    """The vision-pipeline model family: three jax-backed composing models
    plus the ensemble wiring them into a DAG.

    - ``{prefix}_preprocess``: UINT8 NHWC image -> normalized FP32 NCHW
      (direct dispatch: trivially cheap, and its jitted output is already a
      device array, which puts the backbone step on the batcher's device
      path).
    - ``{prefix}_backbone``: resnet features, dynamic batching + fused
      device groups — concurrent pipeline requests fuse into real MXU
      batches mid-DAG.
    - ``{prefix}_postprocess``: classification head + softmax, labels
      attached for the classification extension.
    - ``{prefix}_pipeline``: the ensemble (IMAGE -> SCORES).

    Defaults are the hermetic tiny variant served by the builtin model set;
    ``pipeline_models()`` passes ``image_size=224, stages=_RESNET50_STAGES,
    num_classes=1000`` for the full resnet50-backed pipeline.
    """
    runners = _VisionPipelineRunners(image_size, stages, num_classes)
    labels = [f"class_{i}" for i in range(num_classes)]
    feat = runners.feature_dim
    preprocess = Model(
        f"{prefix}_preprocess",
        inputs=[TensorSpec("IMAGE", "UINT8", [-1, image_size, image_size, 3])],
        outputs=[TensorSpec("PIXELS", "FP32", [-1, 3, image_size, image_size])],
        fn=runners.preprocess,
        platform="jax",
        backend="jax",
        max_batch_size=max_batch_size,
    )
    backbone = Model(
        f"{prefix}_backbone",
        inputs=[TensorSpec("PIXELS", "FP32", [-1, 3, image_size, image_size])],
        outputs=[TensorSpec("FEATURES", "FP32", [-1, feat])],
        fn=runners.backbone,
        platform="jax",
        backend="jax",
        max_batch_size=max_batch_size,
        dynamic_batching=True,
        batch_device_inputs=True,
        warmup=warmup,
    )
    postprocess = Model(
        f"{prefix}_postprocess",
        inputs=[TensorSpec("FEATURES", "FP32", [-1, feat])],
        outputs=[TensorSpec("SCORES", "FP32", [-1, num_classes], labels=labels)],
        fn=runners.postprocess,
        platform="jax",
        backend="jax",
        max_batch_size=max_batch_size,
    )
    pipeline = Model(
        f"{prefix}_pipeline",
        inputs=[TensorSpec("IMAGE", "UINT8", [-1, image_size, image_size, 3])],
        outputs=[TensorSpec("SCORES", "FP32", [-1, num_classes], labels=labels)],
        fn=None,
        platform="ensemble",
        ensemble_steps=[
            {
                "model_name": f"{prefix}_preprocess",
                "input_map": {"IMAGE": "IMAGE"},
                "output_map": {"PIXELS": "pixels"},
            },
            {
                "model_name": f"{prefix}_backbone",
                "input_map": {"PIXELS": "pixels"},
                "output_map": {"FEATURES": "features"},
            },
            {
                "model_name": f"{prefix}_postprocess",
                "input_map": {"FEATURES": "features"},
                "output_map": {"SCORES": "SCORES"},
            },
        ],
    )
    return [preprocess, backbone, postprocess, pipeline]


def resnet50_model(
    name="resnet50", image_size=224, max_batch_size=64, warmup=False
):
    """Servable ResNet-50 (BASELINE.md config 3's model, rebuilt natively in
    JAX rather than loaded from ONNX).  Reference analog: the resnet50
    concurrency sweep perf_analyzer README documents; cited in SURVEY §6."""
    runner = ResNet50Classifier(image_size)
    labels = [f"class_{i}" for i in range(_NUM_CLASSES)]
    return Model(
        name,
        inputs=[TensorSpec("INPUT0", "FP32", [-1, 3, image_size, image_size])],
        outputs=[TensorSpec("OUTPUT0", "FP32", [-1, _NUM_CLASSES], labels=labels)],
        fn=runner,
        platform="jax",
        backend="jax",
        max_batch_size=max_batch_size,
        dynamic_batching=True,
        warmup=warmup,
        batch_device_inputs=True,
        fused_batching=True,
        max_fused_arity=16,
        flops_per_item=resnet50_flops_per_image(image_size),
    )
