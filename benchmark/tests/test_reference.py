"""What ResNet's two comparisons with the plain reference refuse, at the
published widths on the CPU (``configs/resnet50-v1.5.py`` TOLERANCE quotes
these counts). A defect is put on one side only: into the program by
rounding what its modules return (flax's method interceptor; the program's
files are not touched), or into the reference by replacing one of its
functions. Either way the two sides then differ by that defect."""

import flax.linen as nn
import jax.numpy as jnp
import pytest

import files
from horovod_tpu.models.resnet import BottleneckBlock

CONFIG, SEED = "resnet50-v1.5", 5


@pytest.fixture(scope="module")
def setting():
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    variables = model.make_variables(model.make_model(spec), spec, SEED)
    return model, spec, variables


def errors(setting, name, reference=None, interceptor=None):
    """The errors of the check called ``name`` and whether its band holds."""
    model, spec, variables = setting
    reference = reference or files.reference_module(CONFIG)
    params, images, labels, want = model.reference_case(
        spec, variables, reference, SEED)
    measure, net = {
        "reference_f32": (model.errors_whole,
                          model.make_model(spec, jnp.float32)),
        "reference": (model.errors_piecewise, model.make_model(spec))}[name]
    with nn.intercept_methods(interceptor or (
            lambda f, args, kwargs, context: f(*args, **kwargs))):
        err = measure(net, params, variables["batch_stats"], images, labels,
                      want)
    print(f"{name}: {err}")
    return err, all(err[k] <= model.TOLERANCE[name][k] for k in err)


@pytest.mark.parametrize("name", ["reference_f32", "reference"])
def test_the_program_is_inside_both_bands(setting, name):
    assert errors(setting, name)[1]


def rounded(which, dtype):
    """What the modules ``which`` picks return, rounded to ``dtype``."""
    def interceptor(f, args, kwargs, context):
        y = f(*args, **kwargs)
        if which(context.module) and context.method_name == "__call__":
            y = y.astype(dtype).astype(y.dtype)
        return y
    return interceptor


def test_activations_in_an_8_bit_float_miss_the_served_band(setting):
    err, ok = errors(setting, "reference", interceptor=rounded(
        lambda m: isinstance(m, (nn.Conv, nn.BatchNorm)), jnp.float8_e4m3fn))
    band = setting[0].TOLERANCE["reference"]
    assert not ok
    assert err["stem"] > 5 * band["stem"]
    assert err["bottlenecks"] > 5 * band["bottlenecks"]


def test_one_stage_in_bfloat16_misses_the_float32_band(setting):
    third_stage = rounded(lambda m: isinstance(m, BottleneckBlock)
                          and m.filters == 256, jnp.bfloat16)
    assert not errors(setting, "reference_f32", interceptor=third_stage)[1]


def other_eps(reference):
    plain = reference.batch_norm
    reference.batch_norm = lambda x, sb: plain(x, sb, eps=1e-3)


def v1_stride(reference):
    """The stride on the first 1x1 of a down-sampling bottleneck (He et
    al.'s v1) and not on its 3x3."""
    conv, bn, relu = reference.conv, reference.batch_norm, reference.relu

    def bottleneck(x, b):
        y = relu(bn(conv(x, b["conv1"], b["stride"]), b["bn1"]))
        y = relu(bn(conv(y, b["conv2"]), b["bn2"]))
        y = bn(conv(y, b["conv3"]), b["bn3"])
        if "conv_proj" in b:
            x = bn(conv(x, b["conv_proj"], b["stride"]), b["bn_proj"])
        return relu(x + y)

    reference.bottleneck = bottleneck


def torchvision_padding(reference):
    """(1, 1) on the strided 3x3 where the program pads (0, 1)."""
    plain = reference.conv

    def conv(x, w, stride=1, padding="SAME"):
        if stride == 2 and w.shape[0] == 3:
            padding = [(1, 1), (1, 1)]
        return plain(x, w, stride, padding)

    reference.conv = conv


@pytest.mark.parametrize("defect", [other_eps, v1_stride,
                                    torchvision_padding])
def test_another_network_misses_the_float32_band(setting, defect):
    reference = files.reference_module(CONFIG)      # a copy of its own
    defect(reference)
    err, ok = errors(setting, "reference_f32", reference=reference)
    assert not ok
    assert err["logits"] > 5 * setting[0].TOLERANCE["reference_f32"]["logits"]
