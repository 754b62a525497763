"""Command-line application — parity with src/application/application.cpp.

Usage:  python -m lightgbm_tpu config=train.conf [key=value ...]
CLI args override the config file (application.cpp:48-104).  Tasks: train,
predict, convert_model (emits compiled C++ if-else code like
GBDT::ModelToIfElse, or PMML — see run_convert_model).
Snapshots every ``snapshot_freq`` iterations (application.cpp:237-241).
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List

import numpy as np

from .basic import Booster
from .metrics import create_metric
from .models.factory import create_boosting
from .objectives import create_objective
from .io.dataset import TrainingData
from .io import parser as _parser
from .utils.config import Config, key_alias_transform
from .utils.log import Log


def parse_cli_params(argv: List[str]) -> Dict[str, str]:
    """config= file + k=v overrides; CLI wins (application.cpp:48-104)."""
    cli: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            Log.warning("Unknown argument: %s", arg)
            continue
        k, _, v = arg.partition("=")
        cli[k.strip()] = v.strip()
    params: Dict[str, str] = {}
    conf_path = cli.get("config") or cli.get("config_file")
    if conf_path:
        with open(conf_path) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if not line or "=" not in line:
                    continue
                k, _, v = line.partition("=")
                params.setdefault(k.strip(), v.strip())
    params.update(cli)
    params.pop("config", None)
    params.pop("config_file", None)
    return params


def run_train(cfg: Config) -> None:
    if not cfg.data:
        Log.fatal("No training data, application quit")
    # elastic checkpoint/resume (models/checkpoint.py) on the CLI
    # surface too — same contract as engine.train: a compatible
    # checkpoint in checkpoint_dir seeds the model and only the
    # remaining rounds run; an explicit input_model wins.  Peeked before
    # the data load because continuing needs the raw rows kept.
    ck_dir = str(cfg.raw.get("checkpoint_dir", "") or "")
    ck_every = int(cfg.raw.get("checkpoint_every", 0) or 0)
    resume_ck = None
    if ck_dir and not cfg.input_model:
        from .models import checkpoint as ckpt_mod
        resume_ck = ckpt_mod.load_checkpoint(ck_dir)
        if resume_ck is not None:
            ckpt_mod.check_resumable(resume_ck, dict(cfg.raw))
    Log.info("Loading train data...")
    # keep raw rows when continuing: loaded models predict on raw values
    train_td = TrainingData.from_file(
        cfg.data, cfg,
        keep_raw=bool(cfg.input_model) or resume_ck is not None)
    if getattr(train_td, "_binned_reader", None) is not None:
        Log.info("Train data is pre-binned (mmap-backed, %d shard(s), "
                 "zero re-binning)", train_td._binned_reader.num_shards)
    objective = create_objective(cfg.objective, cfg)
    if objective is not None:
        objective.init(train_td.metadata, train_td.num_data)
    training_metrics = []
    if cfg.is_training_metric:
        for name in cfg.metrics():
            m = create_metric(name, cfg)
            if m is not None:
                m.init(train_td.metadata, train_td.num_data)
                training_metrics.append(m)
    booster = create_boosting(cfg.boosting_type, cfg, train_td, objective,
                              training_metrics)
    if cfg.input_model:
        with open(cfg.input_model) as f:
            base = f.read()
        Log.info("Continued training from %s", cfg.input_model)
        booster.load_model_from_string(base)
        booster.reset_training_data(cfg, train_td, objective, training_metrics)
    rounds_done = 0
    if resume_ck is not None:
        booster.load_model_from_string(resume_ck["model"])
        booster.reset_training_data(cfg, train_td, objective,
                                    training_metrics)
        rounds_done = int(resume_ck["iteration"])
        Log.info("Resuming from checkpoint %s: %d round(s) done, "
                 "%d remain", ck_dir, rounds_done,
                 max(0, cfg.num_iterations - rounds_done))
    for i, vf in enumerate(cfg.valid_data or []):
        Log.info("Loading validation data %d...", i + 1)
        valid_td = TrainingData.from_file(vf, cfg, reference=train_td)
        metrics = []
        for name in cfg.metrics():
            m = create_metric(name, cfg)
            if m is not None:
                m.init(valid_td.metadata, valid_td.num_data)
                metrics.append(m)
        booster.add_valid_dataset(valid_td, metrics)
    Log.info("Started training...")
    import time
    # XLA-level tracing: the TIMETAG/#ifdef timers of the reference
    # (gbdt.cpp:21-30, serial_tree_learner.cpp:10-17) become a
    # jax.profiler trace viewable in TensorBoard/Perfetto
    profile_dir = cfg.raw.get("tpu_profile_dir", "")
    if profile_dir:
        import jax
        jax.profiler.start_trace(str(profile_dir))
        Log.info("jax.profiler trace -> %s", profile_dir)
    finished = False
    try:
        for it in range(rounds_done, cfg.num_iterations):
            t0 = time.time()
            stop = booster.train_one_iter(None, None, True)
            Log.info("%f seconds elapsed, finished iteration %d",
                     time.time() - t0, it + 1)
            if cfg.snapshot_freq > 0 and (it + 1) % cfg.snapshot_freq == 0:
                booster.save_model_to_file("%s.snapshot_iter_%d"
                                           % (cfg.output_model, it + 1))
            if ck_every > 0 and ck_dir and (it + 1) % ck_every == 0:
                from .models import checkpoint as ckpt_mod
                path = ckpt_mod.save_checkpoint(ck_dir, booster, it + 1,
                                                dict(cfg.raw))
                if booster._obs.enabled:
                    booster._obs.event(
                        "checkpoint", it=it + 1, path=path,
                        bytes=int(os.path.getsize(path)), world_size=1)
            if stop:
                break
        finished = True
    finally:
        if profile_dir:
            import jax
            jax.profiler.stop_trace()   # keep the trace on failures too
        # finalize run telemetry (lightgbm_tpu/obs): run_end + flush, so a
        # failed run still leaves a readable timeline (status=aborted)
        booster._obs.close(status="ok" if finished else "aborted")
    if cfg.obs_events_path:
        obs = booster._obs
        ep = (str(getattr(obs, "events_path", "") or "")
              or cfg.obs_events_path)
        if getattr(obs, "world_size", 1) > 1:
            Log.info("Telemetry timeline shard (rank %d/%d) -> %s "
                     "(cross-rank view: `python -m lightgbm_tpu obs "
                     "merge %s`)", obs.rank, obs.world_size, ep, ep)
        else:
            Log.info("Telemetry timeline -> %s (query with `python -m "
                     "lightgbm_tpu obs summary %s`)", ep, ep)
    if cfg.obs_metrics_path:
        Log.info("Metrics export -> %s", cfg.obs_metrics_path)
    booster.save_model_to_file(cfg.output_model)
    Log.info("Finished training")


def run_predict(cfg: Config) -> None:
    if not cfg.data:
        Log.fatal("No prediction data, application quit")
    with open(cfg.input_model) as f:
        model_str = f.read()
    booster = Booster(model_str=model_str)
    parsed = _parser.parse_file(cfg.data, has_header=cfg.has_header)
    num_iteration = cfg.num_iteration_predict
    out = booster.predict(parsed.features, num_iteration=num_iteration,
                          raw_score=cfg.is_predict_raw_score,
                          pred_leaf=cfg.is_predict_leaf_index)
    out = np.asarray(out)
    with open(cfg.output_result, "w") as f:
        if out.ndim == 1:
            for v in out:
                f.write("%.9g\n" % v)
        else:
            for row in out:
                f.write("\t".join("%.9g" % v for v in row) + "\n")
    Log.info("Finished prediction, results saved to %s", cfg.output_result)


def run_convert_model(cfg: Config) -> None:
    """Model -> C++ if-else source (GBDT::SaveModelToIfElse path,
    application.cpp ConvertModel)."""
    from .convert_model import model_to_cpp
    with open(cfg.input_model) as f:
        booster = Booster(model_str=f.read())
    with open(cfg.convert_model, "w") as f:
        f.write(model_to_cpp(booster._gbdt))
    Log.info("Model converted to %s", cfg.convert_model)


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if argv and argv[0] == "obs":
        # timeline query subcommand (docs/Observability.md):
        #   python -m lightgbm_tpu obs summary|recompiles|stragglers|
        #                              diff|trace ...
        from .obs.query import main as obs_main
        return obs_main(argv[1:])
    if argv and argv[0] == "lint":
        # graftlint static analyzer (docs/StaticAnalysis.md):
        #   python -m lightgbm_tpu lint [--check] [--json] [--baseline F]
        from .analysis.cli import main as lint_main
        return lint_main(argv[1:])
    params = parse_cli_params(argv)
    params = key_alias_transform(params, raise_unknown=False)
    cfg = Config(params)
    task = params.get("task", "train")
    if task in ("train", "predict", "prediction", "test"):
        # before the first compile: a second CLI run of the same shape
        # loads its programs instead of compiling them
        from .utils.common import enable_compilation_cache
        enable_compilation_cache()
    if task == "train":
        run_train(cfg)
    elif task in ("predict", "prediction", "test"):
        run_predict(cfg)
    elif task == "convert_model":
        run_convert_model(cfg)
    else:
        Log.fatal("Unknown task: %s", task)
    return 0


def console_entry() -> None:
    """setuptools console-script entry (pyproject.toml)."""
    sys.exit(main())


if __name__ == "__main__":
    console_entry()
