"""Contrastive-divergence path: kRBM layer, CDTrainer, kEuclideanLoss,
and the unroll-to-autoencoder recipe (the reference
declares alg kContrastiveDivergence, model.proto:40-44, but never built
the worker; this is the greenfield fill)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.config import load_model_config, parse_model_config
from singa_tpu.config.schema import ConfigError
from singa_tpu.data.loader import synthetic_arrays, write_records
from singa_tpu.graph.builder import build_net
from singa_tpu.trainer import CDTrainer, Trainer, make_trainer
from singa_tpu.trainer.cd import unroll_autoencoder

RBM_CONF = """
name: "test-rbm"
train_steps: {train_steps}
test_steps: 2
alg: kContrastiveDivergence
updater {{
  base_learning_rate: 0.1
  learning_rate_change_method: kFixed
  momentum: 0.5
  type: kSGD
}}
neuralnet {{
  layer {{
    name: "data"
    type: "kShardData"
    data_param {{ path: "{train_shard}" batchsize: 64 }}
    exclude: kTest
  }}
  layer {{
    name: "data"
    type: "kShardData"
    data_param {{ path: "{test_shard}" batchsize: 64 }}
    exclude: kTrain
  }}
  layer {{
    name: "mnist"
    type: "kMnistImage"
    srclayers: "data"
    mnist_param {{ norm_a: 255 norm_b: 0 }}
  }}
  layer {{
    name: "rbm1"
    type: "kRBM"
    srclayers: "mnist"
    rbm_param {{ num_hidden: 48 cd_k: 1 }}
    param {{ name: "weight" init_method: kGaussain mean: 0 std: 0.1 }}
    param {{ name: "vbias" init_method: kConstant value: 0 }}
    param {{ name: "hbias" init_method: kConstant value: 0 }}
  }}
  layer {{
    name: "rbm2"
    type: "kRBM"
    srclayers: "rbm1"
    rbm_param {{ num_hidden: 16 cd_k: 2 }}
    param {{ name: "weight" init_method: kGaussain mean: 0 std: 0.1 }}
    param {{ name: "vbias" init_method: kConstant value: 0 }}
    param {{ name: "hbias" init_method: kConstant value: 0 }}
  }}
}}
"""


def make_rbm_conf(tmp_path, train_steps=80):
    train_dir = str(tmp_path / "train_shard")
    test_dir = str(tmp_path / "test_shard")
    write_records(train_dir, *synthetic_arrays(512, seed=1))
    write_records(test_dir, *synthetic_arrays(128, seed=1, noise_seed=2))
    return parse_model_config(
        RBM_CONF.format(
            train_shard=train_dir, test_shard=test_dir,
            train_steps=train_steps,
        )
    )


def _recon(trainer):
    avg = trainer.evaluate(trainer.test_net, 2, "test", 0)
    return {name: m["loss"] for name, m in avg.items()}


class TestCDTrainer:
    def test_stacked_cd_reduces_reconstruction_error(self, tmp_path):
        # 200 steps: rbm2 first chases rbm1's moving hidden distribution
        # (its error transiently rises), then both settle below their
        # initial reconstruction error
        cfg = make_rbm_conf(tmp_path, train_steps=200)
        t = CDTrainer(cfg, seed=0, log=lambda s: None, prefetch=False)
        before = _recon(t)
        t.run()
        after = _recon(t)
        assert set(after) == {"rbm1", "rbm2"}
        assert after["rbm1"] < 0.5 * before["rbm1"], (before, after)
        assert after["rbm2"] < before["rbm2"], (before, after)

    def test_make_trainer_dispatches_on_alg(self, tmp_path):
        cfg = make_rbm_conf(tmp_path, train_steps=2)
        t = make_trainer(cfg, log=lambda s: None, prefetch=False)
        assert isinstance(t, CDTrainer)

    def test_requires_rbm_layer(self, tmp_path):
        from test_trainer import make_conf

        data = (
            synthetic_arrays(128, seed=1),
            synthetic_arrays(64, seed=1, noise_seed=2),
        )
        cfg = make_conf(tmp_path, *data, train_steps=2)
        cfg.alg = "kContrastiveDivergence"
        with pytest.raises(ConfigError):
            CDTrainer(cfg, log=lambda s: None, prefetch=False)


class TestEuclideanLoss:
    def test_math(self):
        from singa_tpu.config.schema import LayerConfig
        from singa_tpu.layers import create_layer

        cfg = LayerConfig()
        cfg.name = "loss"
        cfg.type = "kEuclideanLoss"
        cfg.srclayers = ["pred", "target"]
        layer = create_layer(cfg)
        layer.setup([(4, 3), (4, 3)], 4)
        pred = jnp.ones((4, 3))
        target = jnp.zeros((4, 3))
        loss, metrics = layer.apply({}, [pred, target], training=True)
        # 0.5 * mean_over_batch(sum_sq) = 0.5 * 3
        assert float(loss) == pytest.approx(1.5)
        assert float(metrics["loss"]) == pytest.approx(1.5)

    def test_rejects_mismatched_sizes(self):
        from singa_tpu.config.schema import LayerConfig
        from singa_tpu.layers import create_layer

        cfg = LayerConfig()
        cfg.name = "loss"
        cfg.type = "kEuclideanLoss"
        cfg.srclayers = ["a", "b"]
        layer = create_layer(cfg)
        with pytest.raises(ConfigError):
            layer.setup([(4, 3), (4, 5)], 4)


class TestUnroll:
    def test_unrolled_autoencoder_finetunes(self, tmp_path):
        # 1. pretrain a tiny stack
        cfg = make_rbm_conf(tmp_path, train_steps=40)
        t = CDTrainer(cfg, seed=0, log=lambda s: None, prefetch=False)
        t.run()
        from singa_tpu.trainer import save_checkpoint

        ck = str(tmp_path / "rbm.npz")
        save_checkpoint(ck, 40, t.params)
        ae_init = str(tmp_path / "ae_init.npz")
        unroll_autoencoder(ck, ae_init, [("rbm1", "dec1"), ("rbm2", "dec2")])

        # 2. fine-tune the unrolled net with BP + kEuclideanLoss
        ae_conf = """
name: "test-ae"
train_steps: 30
test_steps: 2
checkpoint: "%s"
updater {
  base_learning_rate: 0.05
  learning_rate_change_method: kFixed
  momentum: 0.9
  type: kSGD
}
neuralnet {
  layer { name: "data" type: "kShardData"
          data_param { path: "%s" batchsize: 64 } exclude: kTest }
  layer { name: "data" type: "kShardData"
          data_param { path: "%s" batchsize: 64 } exclude: kTrain }
  layer { name: "mnist" type: "kMnistImage" srclayers: "data"
          mnist_param { norm_a: 255 norm_b: 0 } }
  layer { name: "rbm1" type: "kInnerProduct" srclayers: "mnist"
          inner_product_param { num_output: 48 }
          param { name: "weight" init_method: kPretrained }
          param { name: "bias" init_method: kPretrained } }
  layer { name: "sig1" type: "kSigmoid" srclayers: "rbm1" }
  layer { name: "rbm2" type: "kInnerProduct" srclayers: "sig1"
          inner_product_param { num_output: 16 }
          param { name: "weight" init_method: kPretrained }
          param { name: "bias" init_method: kPretrained } }
  layer { name: "dec2" type: "kInnerProduct" srclayers: "rbm2"
          inner_product_param { num_output: 48 }
          param { name: "weight" init_method: kPretrained }
          param { name: "bias" init_method: kPretrained } }
  layer { name: "dsig2" type: "kSigmoid" srclayers: "dec2" }
  layer { name: "dec1" type: "kInnerProduct" srclayers: "dsig2"
          inner_product_param { num_output: 784 }
          param { name: "weight" init_method: kPretrained }
          param { name: "bias" init_method: kPretrained } }
  layer { name: "dsig1" type: "kSigmoid" srclayers: "dec1" }
  layer { name: "loss" type: "kEuclideanLoss"
          srclayers: "dsig1" srclayers: "mnist" }
}
""" % (ae_init, str(tmp_path / "train_shard"), str(tmp_path / "test_shard"))
        ae_cfg = parse_model_config(ae_conf)
        ae = Trainer(ae_cfg, seed=0, log=lambda s: None, prefetch=False)
        # step counter starts fresh (unroll writes step 0)
        assert ae.start_step == 0
        # encoder weights came from the pretrained stack...
        np.testing.assert_allclose(
            np.asarray(ae.params["rbm1/weight"]),
            np.asarray(t.params["rbm1/weight"]),
            rtol=1e-6,
        )
        # ...and decoder weights are their transposes + visible biases
        np.testing.assert_allclose(
            np.asarray(ae.params["dec1/weight"]),
            np.asarray(t.params["rbm1/weight"]).T,
            rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(ae.params["dec2/bias"]),
            np.asarray(t.params["rbm2/vbias"]),
            rtol=1e-6,
        )
        before = ae.evaluate(ae.test_net, 2, "test", 0)["loss"]["loss"]
        ae.run()
        after = ae.evaluate(ae.test_net, 2, "test", 30)["loss"]["loss"]
        assert after < before


class TestRepoConfs:
    def test_rbm_conf_parses_and_builds(self, tmp_path):
        conf = os.path.join(
            os.path.dirname(__file__), "..", "examples", "mnist", "rbm.conf"
        )
        cfg = load_model_config(conf)
        assert cfg.alg == "kContrastiveDivergence"
        shard = str(tmp_path / "shard")
        write_records(shard, *synthetic_arrays(64, seed=0))
        for layer in cfg.neuralnet.layer:
            if layer.type == "kShardData":
                layer.data_param.path = shard
        net = build_net(cfg, "kTrain")
        assert [l.name for l in net.layers][-4:] == [
            "rbm1", "rbm2", "rbm3", "rbm4",
        ]
        assert net.layers[-1].out_shape == (100, 30)

    def test_autoencoder_conf_parses_and_builds(self, tmp_path):
        conf = os.path.join(
            os.path.dirname(__file__), "..", "examples", "mnist",
            "autoencoder.conf",
        )
        cfg = load_model_config(conf)
        cfg.checkpoint = ""  # built without the pretrained init here
        shard = str(tmp_path / "shard")
        write_records(shard, *synthetic_arrays(64, seed=0))
        for layer in cfg.neuralnet.layer:
            if layer.type == "kShardData":
                layer.data_param.path = shard
        net = build_net(cfg, "kTrain")
        assert net.layers[-1].TYPE == "kEuclideanLoss"
        # the unrolled shape comes back to 784 pixels
        assert net.name2layer["dec1"].out_shape == (100, 784)
