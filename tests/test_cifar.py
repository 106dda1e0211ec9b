"""CIFAR-10 path: cifar binary loader, meanfile,
RGB parser with mean subtraction, and the AlexNet-style example conf."""

import os

import numpy as np
import pytest

from singa_tpu.config import load_model_config, parse_cluster_config
from singa_tpu.data.loader import (
    compute_mean,
    read_cifar_bins,
    structured_rgb,
    synthetic_arrays,
    write_records,
)
from singa_tpu.data.pipeline import load_shard_arrays
from singa_tpu.graph.builder import build_net
from singa_tpu.trainer import Trainer

REPO = os.path.join(os.path.dirname(__file__), "..")


def fake_cifar_bin(path, n, seed=0):
    """Write a CIFAR-10-format binary batch of n synthetic records."""
    images, labels = synthetic_arrays(n, size=32, channels=3, seed=seed)
    rows = np.concatenate(
        [labels[:, None], images.reshape(n, -1)], axis=1
    ).astype(np.uint8)
    rows.tofile(path)
    return images, labels


class TestCifarLoader:
    def test_bin_roundtrip_through_shard(self, tmp_path):
        binf = str(tmp_path / "data_batch_1.bin")
        images, labels = fake_cifar_bin(binf, 50)
        got_i, got_l = read_cifar_bins([binf])
        np.testing.assert_array_equal(got_i, images)
        np.testing.assert_array_equal(got_l, labels)
        shard = str(tmp_path / "shard")
        write_records(shard, got_i, got_l)
        loaded_i, loaded_l = load_shard_arrays(shard)
        assert loaded_i.shape == (50, 3, 32, 32)
        np.testing.assert_array_equal(loaded_i, images.astype(np.float32))
        np.testing.assert_array_equal(loaded_l, labels)

    def test_multiple_bins_concatenate(self, tmp_path):
        b1 = str(tmp_path / "b1.bin")
        b2 = str(tmp_path / "b2.bin")
        fake_cifar_bin(b1, 20, seed=1)
        fake_cifar_bin(b2, 30, seed=2)
        images, labels = read_cifar_bins([b1, b2])
        assert images.shape == (50, 3, 32, 32)
        assert labels.shape == (50,)

    def test_truncated_bin_rejected(self, tmp_path):
        binf = str(tmp_path / "bad.bin")
        np.zeros(3073 * 2 + 1, dtype=np.uint8).tofile(binf)
        with pytest.raises(ValueError):
            read_cifar_bins([binf])

    def test_compute_mean(self, tmp_path):
        shard = str(tmp_path / "shard")
        images, labels = synthetic_arrays(40, size=32, channels=3, seed=3)
        write_records(shard, images, labels)
        out = str(tmp_path / "mean.npy")
        mean = compute_mean(shard, out)
        assert mean.shape == (3, 32, 32)
        np.testing.assert_allclose(
            mean, images.astype(np.float64).mean(axis=0), rtol=1e-5
        )
        assert os.path.exists(out)


class TestMeanfileParser:
    def test_rgb_parser_subtracts_mean(self, tmp_path):
        from singa_tpu.config.schema import LayerConfig
        from singa_tpu.layers import create_layer
        import jax.numpy as jnp

        mean = np.full((3, 8, 8), 10.0, dtype=np.float32)
        mpath = str(tmp_path / "mean.npy")
        np.save(mpath, mean)
        cfg = LayerConfig()
        cfg.name = "rgb"
        cfg.type = "kRGBImage"
        cfg.srclayers = ["data"]
        from singa_tpu.config import parse_model_config

        layer = create_layer(cfg)
        layer.cfg.rgbimage_param = type(cfg).FIELDS[
            "rgbimage_param"
        ].message()
        layer.cfg.rgbimage_param.meanfile = mpath
        layer.setup([(4, 3, 8, 8)], 4)
        x = jnp.full((4, 3, 8, 8), 30.0)
        out = layer.apply({}, [{"image": x}], training=False)
        np.testing.assert_allclose(np.asarray(out), 20.0)

    def test_mean_shape_mismatch_rejected(self, tmp_path):
        from singa_tpu.config.schema import ConfigError, LayerConfig
        from singa_tpu.layers import create_layer

        np.save(str(tmp_path / "mean.npy"), np.zeros((3, 4, 4), np.float32))
        cfg = LayerConfig()
        cfg.name = "rgb"
        cfg.type = "kRGBImage"
        cfg.srclayers = ["data"]
        layer = create_layer(cfg)
        layer.cfg.rgbimage_param = type(cfg).FIELDS[
            "rgbimage_param"
        ].message()
        layer.cfg.rgbimage_param.meanfile = str(tmp_path / "mean.npy")
        with pytest.raises(ConfigError):
            layer.setup([(4, 3, 8, 8)], 4)


def _prep_alexnet(tmp_path, train_steps, batchsize=50, n=400):
    cfg = load_model_config(
        os.path.join(REPO, "examples", "cifar10", "alexnet.conf")
    )
    train = str(tmp_path / "train_shard")
    test = str(tmp_path / "test_shard")
    write_records(
        train, *synthetic_arrays(n, size=32, channels=3, seed=1)
    )
    write_records(
        test,
        *synthetic_arrays(128, size=32, channels=3, seed=1, noise_seed=2),
    )
    mpath = str(tmp_path / "mean.npy")
    compute_mean(train, mpath)
    for layer in cfg.neuralnet.layer:
        if layer.type == "kShardData":
            layer.data_param.path = (
                train if "kTest" in layer.exclude else test
            )
            layer.data_param.batchsize = batchsize
            layer.data_param.random_skip = 0
        if layer.type == "kRGBImage":
            layer.rgbimage_param.meanfile = mpath
    cfg.train_steps = train_steps
    cfg.test_steps = 2
    cfg.test_frequency = 0
    cfg.checkpoint_frequency = 0
    cfg.updater.base_learning_rate = 0.01
    cfg.updater.learning_rate_change_method = "kFixed"
    return cfg


class TestAlexNet:
    def test_conf_builds_with_expected_shapes(self, tmp_path):
        cfg = _prep_alexnet(tmp_path, train_steps=1)
        net = build_net(cfg, "kTrain")
        # crop 28, ceil-mode pooling (layer.cc:498-501):
        # 28 -> pool1 14 -> pool2 7 -> pool3 3
        assert net.name2layer["rgb"].out_shape == (50, 3, 28, 28)
        assert net.name2layer["pool1"].out_shape == (50, 32, 14, 14)
        assert net.name2layer["pool3"].out_shape == (50, 64, 3, 3)
        assert net.name2layer["fc10"].out_shape == (50, 10)

    def test_trains_synthetic_to_high_accuracy(self, tmp_path):
        # batch 32 (r5, was 64): halves the dominant cost — 99 steps of
        # AlexNet convs at 0.73 s/step on this 1-core host — with the
        # same >0.9 oracle (measured 1.000 at lr 0.0015; the old
        # batch-64/lr-0.002 pair read 0.969). conv1 std widened from
        # the conf's 1e-4 so 100 steps suffice.
        from singa_tpu.data.loader import write_records

        cfg = _prep_alexnet(tmp_path, train_steps=100, batchsize=32)
        write_records(
            str(tmp_path / "train_shard"),
            *structured_rgb(400, seed=1),
            append=False,
        )
        write_records(
            str(tmp_path / "test_shard"),
            *structured_rgb(128, seed=1, noise_seed=2),
            append=False,
        )
        compute_mean(
            str(tmp_path / "train_shard"), str(tmp_path / "mean.npy")
        )
        cfg.updater.base_learning_rate = 0.0015
        for layer in cfg.neuralnet.layer:
            if layer.type == "kConvolution" and layer.name == "conv1":
                layer.param[0].std = 0.01
        t = Trainer(cfg, seed=0, log=lambda s: None, prefetch=False)
        t.run()
        avg = t.evaluate(t.test_net, 2, "test", cfg.train_steps)
        (m,) = avg.values()
        assert m["precision"] > 0.9  # 10 classes, chance = 0.1

    def test_cluster_conf_maps_to_8way_data_mesh(self):
        cluster = parse_cluster_config(
            open(
                os.path.join(REPO, "examples", "cifar10", "cluster.conf")
            ).read()
        )
        assert cluster.ngroups == 8
        assert cluster.synchronous
