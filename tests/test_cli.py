"""The pipeline CLI end-to-end: simulate → featurize → train → predict →
synthesize → anomaly, each through the argparse entry point (the reference
drives these stages as bare scripts; SURVEY.md §3.3)."""

import json
import os

import numpy as np
import pytest

from deeprest_tpu.cli import main
from deeprest_tpu.data.featurize import FeaturizedData


@pytest.fixture(scope="module")
def pipeline(tmp_path_factory):
    """Run the full chain once; individual tests assert on the artifacts."""
    root = tmp_path_factory.mktemp("cli")
    raw = str(root / "raw.jsonl")
    feats = str(root / "input.npz")
    ckpt = str(root / "ckpt")
    plots = str(root / "plots")
    preds = str(root / "preds.npz")

    assert main(["simulate", "--scenario=normal", "--ticks=140",
                 f"--out={raw}"]) == 0
    assert main(["featurize", f"--raw={raw}", f"--out={feats}",
                 "--round-to=8"]) == 0
    assert main(["train", f"--features={feats}", "--epochs=2",
                 "--batch-size=16", "--window=20", "--hidden-size=16",
                 "--dropout=0.1", "--no-baselines",
                 f"--ckpt-dir={ckpt}", f"--plots-dir={plots}"]) == 0
    assert main(["predict", f"--features={feats}",
                 f"--ckpt-dir={ckpt}", f"--out={preds}"]) == 0
    return {"raw": raw, "feats": feats, "ckpt": ckpt, "plots": plots,
            "preds": preds, "root": root}


@pytest.mark.slow
def test_simulate_and_featurize_artifacts(pipeline):
    data = FeaturizedData.load(pipeline["feats"])
    assert data.traffic.shape[0] == 140
    assert data.traffic.shape[1] % 8 == 0
    assert len(data.metric_names) > 10
    # round-trip preserves the space: re-save and reload identical
    again = str(pipeline["root"] / "again.npz")
    data.save(again)
    data2 = FeaturizedData.load(again)
    assert np.array_equal(data.traffic, data2.traffic)
    assert data.space.to_dict() == data2.space.to_dict()


@pytest.mark.slow
def test_train_artifacts(pipeline):
    assert os.path.isdir(pipeline["ckpt"])
    assert any(name.startswith("step_") for name in os.listdir(pipeline["ckpt"]))
    assert os.path.exists(os.path.join(pipeline["plots"], "learning_curve.png"))
    pngs = [f for f in os.listdir(pipeline["plots"]) if f.endswith(".png")]
    data = FeaturizedData.load(pipeline["feats"])
    assert len(pngs) == len(data.metric_names) + 1   # + learning curve


@pytest.mark.slow
def test_predict_artifacts(pipeline):
    data = FeaturizedData.load(pipeline["feats"])
    with np.load(pipeline["preds"]) as z:
        preds = z["predictions"]
        names = [str(n) for n in z["metric_names"]]
    assert names == data.metric_names
    assert preds.shape == (140, len(names), 3)
    assert np.all(np.isfinite(preds))


@pytest.mark.slow
def test_synthesize_from_raw(pipeline, capsys):
    out = str(pipeline["root"] / "synthetic.npz")
    data = FeaturizedData.load(pipeline["feats"])
    endpoint = data.space.endpoints()[0]
    rc = main(["synthesize", f"--raw={pipeline['raw']}", "--round-to=8",
               f"--mix={json.dumps({endpoint: 7})}", "--ticks=9",
               f"--out={out}"])
    assert rc == 0
    with np.load(out) as z:
        series = z["traffic"]
    assert series.shape[0] == 9
    # every step has >= count of the root path (children add more)
    assert np.all(series.sum(axis=1) >= 7)


@pytest.mark.slow
def test_anomaly_command_contract(pipeline, capsys):
    # Detector quality is covered in test_serve.py; here: the command runs,
    # emits one report per metric plus a JSON summary, and exit code stays 0
    # without --fail-on-anomaly regardless of flags (2-epoch model).
    rc = main(["anomaly", f"--features={pipeline['feats']}",
               f"--ckpt-dir={pipeline['ckpt']}"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    payload = json.loads(out[-1])
    data = FeaturizedData.load(pipeline["feats"])
    assert len(out) == len(data.metric_names) + 1
    assert set(payload["flagged"]) <= set(data.metric_names)


def test_featurize_requires_input():
    with pytest.raises(SystemExit):
        main(["featurize"])


@pytest.mark.slow
def test_predict_raw_uses_checkpoint_space(pipeline):
    """--raw at serve time must featurize against the checkpoint's space,
    not a freshly grown vocabulary (whose column order depends on corpus
    observation order)."""
    from deeprest_tpu.serve.predictor import Predictor

    pred = Predictor.from_checkpoint(pipeline["ckpt"])
    space = pred.space()
    assert space is not None
    assert space.capacity == pred.model.config.feature_dim
    # a different corpus (crypto scenario) through the raw path
    raw2 = str(pipeline["root"] / "raw2.jsonl")
    out2 = str(pipeline["root"] / "preds2.npz")
    assert main(["simulate", "--scenario=crypto", "--ticks=25",
                 f"--out={raw2}"]) == 0
    assert main(["predict", f"--raw={raw2}", f"--ckpt-dir={pipeline['ckpt']}",
                 f"--out={out2}"]) == 0
    with np.load(out2) as z:
        assert z["predictions"].shape == (25, len(pred.metric_names), 3)


@pytest.mark.slow
def test_predict_rejects_mismatched_vocabulary(pipeline, tmp_path):
    """--features extracted with a different vocabulary (same width) must be
    rejected, not silently fed to the model with permuted columns."""
    raw2 = str(tmp_path / "raw2.jsonl")
    feats2 = str(tmp_path / "feats2.npz")
    assert main(["simulate", "--scenario=composition", "--ticks=30", "--seed=3",
                 f"--out={raw2}"]) == 0
    # same round-to → same capacity, different observation order
    assert main(["featurize", f"--raw={raw2}", f"--out={feats2}",
                 "--round-to=8"]) == 0
    with pytest.raises(SystemExit, match="vocabulary"):
        main(["predict", f"--features={feats2}",
              f"--ckpt-dir={pipeline['ckpt']}", "--out=x.npz"])


def test_featurize_out_without_extension(tmp_path):
    raw = str(tmp_path / "raw.jsonl")
    assert main(["simulate", "--ticks=5", f"--out={raw}"]) == 0
    rc = main(["featurize", f"--raw={raw}", f"--out={tmp_path / 'feats'}",
               "--round-to=8"])
    assert rc == 0
    # save appended .npz and load resolves the bare name too
    data = FeaturizedData.load(str(tmp_path / "feats"))
    assert data.traffic.shape[0] == 5


@pytest.mark.slow
def test_whatif_command_and_sweep(pipeline, capsys, tmp_path):
    """`whatif` estimates a hypothetical mix; `--sweep` runs the batched
    capacity grid through the fused multi-scenario pipeline."""
    compose = "nginx-thrift_/wrk2-api/post/compose"
    mix = json.dumps({compose: 10})
    assert main(["whatif", f"--ckpt-dir={pipeline['ckpt']}",
                 f"--raw={pipeline['raw']}", f"--mix={mix}",
                 "--ticks=24"]) == 0
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert info["ticks"] == 24
    assert all(set(q) == {"q05", "q50", "q95"}
               for q in info["peaks"].values())

    out = str(tmp_path / "sweep.json")
    assert main(["whatif", f"--ckpt-dir={pipeline['ckpt']}",
                 f"--raw={pipeline['raw']}", f"--mix={mix}",
                 "--ticks=24", "--sweep=0.5,1,2", f"--out={out}"]) == 0
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [r["factor"] for r in info["sweep"]] == [0.5, 1.0, 2.0]
    assert json.load(open(out))["sweep"] == info["sweep"]

    with pytest.raises(SystemExit):   # unknown endpoint is a clean error
        main(["whatif", f"--ckpt-dir={pipeline['ckpt']}",
              f"--raw={pipeline['raw']}", '--mix={"nope": 1}',
              "--ticks=24"])


@pytest.mark.slow
def test_train_profile_capture(pipeline, tmp_path):
    """--profile-dir captures a jax.profiler trace of one epoch (the
    second; the only one here) and reads it back into layers.json
    (SURVEY.md §5.1: the ML-plane profiling the reference lacks)."""
    import glob

    profile_dir = str(tmp_path / "profile")
    assert main(["train", f"--features={pipeline['feats']}", "--epochs=1",
                 "--batch-size=16", "--window=20", "--hidden-size=8",
                 "--no-baselines", f"--profile-dir={profile_dir}"]) == 0
    planes = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                       recursive=True)
    assert planes, f"no xplane artifact under {profile_dir}"
    assert os.path.getsize(planes[0]) > 0
    assert os.path.exists(os.path.join(profile_dir, "layers.json"))


@pytest.mark.slow
def test_train_mesh_flag_runs_sharded(pipeline, tmp_path):
    """--mesh lays the full (data, expert, model) mesh under the train CLI
    (8 virtual CPU devices via conftest)."""
    ckpt = str(tmp_path / "ckpt_mesh")
    assert main(["train", f"--features={pipeline['feats']}", "--epochs=1",
                 "--batch-size=16", "--window=20", "--hidden-size=8",
                 "--no-baselines", "--mesh", "2,2,2",
                 f"--ckpt-dir={ckpt}"]) == 0
    assert any(n.startswith("step_") for n in os.listdir(ckpt))


@pytest.mark.slow
def test_train_mesh_flag_rejects_garbage(pipeline):
    import pytest

    with pytest.raises(SystemExit):
        main(["train", f"--features={pipeline['feats']}", "--mesh", "lots"])
