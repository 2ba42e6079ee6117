"""The plain reference against the served program at a tiny size, and the
control (the reference with bfloat16 weights) in the program's place at each
configuration's own size, judged by the harness's own checks."""

import numpy as np
import pytest

from _bench_tiny import harness, tiny_config
from bench.models import snn_mlp, snn_mlp_ref
from bench.traffic import Traffic, input_sensor


def test_reference_quantization_is_the_programs():
    from repro.configs import menage_paper
    from repro.core.accelerator import map_model
    spec = harness.Spec()
    cfg = tiny_config(spec, "cifar10dvs_mlp")
    weights = snn_mlp.make_weights(cfg, 2**31 + 3)
    mapped = map_model(weights, getattr(menage_paper, cfg["accelerator"]),
                       quant_bits=cfg["quant_bits"])
    for layer, w in zip(mapped.pack().layers, weights):
        got = np.asarray(sum(np.asarray(r.w_dense) for r in layer.rounds))
        want = snn_mlp_ref.quantize(w, cfg["quant_bits"])
        assert np.array_equal(got[:, :w.shape[1]], want)


def test_reference_equals_the_served_forward_bit_for_bit():
    from repro.engine import run_batched
    spec = harness.Spec()
    cfg = tiny_config(spec, "nmnist_mlp")
    seed = 2**32 + 17
    weights = snn_mlp.make_weights(cfg, seed)
    packed = snn_mlp.build(cfg, weights)
    tr = Traffic(input_sensor(cfg), spec.traffic("closed128"), seed)
    rasters = [tr.request(i) for i in range(8)]
    ref, events = snn_mlp_ref.forward(cfg, weights, rasters)
    assert events[0] == sum(int(r.sum()) for r in rasters)
    assert sum(int(r.sum()) for r in ref) > 0
    for raster, want in zip(rasters, ref):
        got = run_batched(packed, raster[None].astype(np.float32),
                          with_stats=False).out_spikes[0]
        assert np.array_equal(got > 0, want)


@pytest.mark.parametrize("config", ["nmnist_mlp", "cifar10dvs_mlp"])
def test_control_in_bfloat16_fails_the_comparison_at_full_size(config):
    """Served answers equal to the reference's are correct; the control's,
    through the same checks and limits, are not."""
    spec = harness.Spec()
    cfg = spec.config(config)
    seed = 2**31 + 41
    weights = snn_mlp.make_weights(cfg, seed)
    tr = Traffic(input_sensor(cfg), spec.traffic("closed128"), seed)
    ids = np.arange(24)
    ref, _ = snn_mlp_ref.forward(cfg, weights, [tr.request(i) for i in ids])
    blobs = [np.packbits(r, axis=None).tobytes() for r in ref]
    rec = {"status": np.zeros(len(ids), np.int8),
           "bits": np.frombuffer(b"".join(blobs), np.uint8),
           "offsets": np.cumsum([0] + [len(b) for b in blobs])}
    served = harness.checks_of(
        harness.compare(cfg, weights, tr, rec, ids, snn_mlp_ref),
        rec["status"])
    assert harness.is_correct(served), served
    ctl = harness.checks_of(
        harness.compare(cfg, weights, tr, rec, ids, snn_mlp_ref,
                        snn_mlp_ref.LOWER_PRECISION["float32"]),
        rec["status"])
    assert not harness.is_correct(ctl), ctl
    assert ctl["wrong_answers"]["value"] > ctl["wrong_answers"]["limit"]
