"""Command-line interface.

Counterpart of the reference CLI
(`/root/reference/src/main.cpp:4-23` → ``Application``,
`src/application/application.cpp:49-82` config parsing, `:239-342`
InitTrain/Train/Predict): reads the same ``key=value`` config-file format
(``train.conf``), supports ``task=train|predict|refit|convert_model``
(`config.h:89-91`), data/valid files with ``.weight``/``.query`` side
files, model save/load, and the fork's snapshot behavior — extended
with resume: ``--resume`` (or ``resume_from=<path|prefix|dir|auto>``)
restarts a preempted run from its newest VALID snapshot and continues
to the original ``num_iterations`` target (README "Fault tolerance").

Usage:
    python -m lightgbm_tpu config=train.conf [key=value ...] [--resume]
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from .config import Config, canonicalize_params
from .utils.log import log_info, log_warning, set_verbosity


def parse_cli_args(argv: List[str]) -> Dict[str, str]:
    """argv ``key=value`` pairs + optional config file (application.cpp:49-82:
    CLI args override config-file values)."""
    kv: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            if arg.lstrip("-") == "resume":
                # `--resume` (bare): pick up the newest valid snapshot
                # under the output_model prefix
                kv["resume_from"] = "auto"
                continue
            log_warning(f"unknown argument {arg!r} (expected key=value)")
            continue
        k, v = arg.split("=", 1)
        kv[k.strip().lstrip("-")] = v.strip()
    file_kv: Dict[str, str] = {}
    cfg_path = kv.get("config", kv.get("config_file"))
    if cfg_path:
        file_kv = parse_config_file(cfg_path)
    file_kv.update(kv)      # CLI wins
    file_kv.pop("config", None)
    file_kv.pop("config_file", None)
    return file_kv


def parse_config_file(path: str) -> Dict[str, str]:
    """key=value lines, '#' comments (application.cpp:60-77)."""
    out: Dict[str, str] = {}
    from .utils.file_io import open_read
    with open_read(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def run(argv: List[str]) -> int:
    params = parse_cli_args(argv)
    cfg = Config.from_params(params)
    set_verbosity(cfg.verbose)
    if cfg.telemetry_output:
        # telemetry_output=<path>: stream the JSONL event trace there
        # (per-rank suffixed once the mesh is up) and write
        # <path>.summary.json after training (rank-0 merged summary in
        # multi-host runs) — README "Observability"
        from . import obs
        obs.enable(trace_path=cfg.telemetry_output)
    task = cfg.task
    if cfg.num_machines > 1:
        _init_network(cfg)
    if task == "train":
        _run_train(cfg, params)
    elif task in ("predict", "prediction", "test"):
        _run_predict(cfg, params)
    elif task == "refit":
        _run_refit(cfg, params)
    elif task == "convert_model":
        _run_convert(cfg, params)
    else:
        raise ValueError(f"unknown task {task!r}")
    return 0


def _init_network(cfg: Config) -> None:
    """Reference Application -> Network::Init (application.cpp:249-254 +
    linkers_socket.cpp): every machine runs the SAME conf; the machine
    list (machines= or machine_list_file=) names the world, the first
    entry is the rendezvous coordinator, and each process resolves its
    own rank by finding its local endpoint in the list."""
    # already-meshed check WITHOUT touching the backend
    # (jax.process_count() would initialize XLA, and
    # jax.distributed.initialize must come first)
    import jax
    if jax.distributed.is_initialized():
        return                              # environment already meshed
    from .parallel.mesh import init_distributed_from_machines
    machines = cfg.machines
    if not machines and cfg.machine_list_file:
        from .utils.file_io import open_read
        with open_read(cfg.machine_list_file) as f:
            # reference mlist.txt lines are space-separated "ip port"
            # (examples/parallel_learning/mlist.txt); normalize to the
            # machines= "ip:port" form
            machines = ",".join(
                ":".join(ln.split()) for ln in f if ln.strip())
    if not machines:
        raise ValueError(
            "num_machines > 1 needs machines=ip:port,... or "
            "machine_list_file= (reference mlist.txt semantics)")
    init_distributed_from_machines(machines, cfg.local_listen_port,
                                   cfg.num_machines)
    import jax
    log_info(f"distributed: rank {jax.process_index()} of "
             f"{jax.process_count()} joined the mesh")


def _run_train(cfg: Config, params) -> None:
    from .basic import Booster, Dataset
    from .engine import train

    if not cfg.data:
        raise ValueError("task=train requires data=<file>")
    train_set = Dataset(cfg.data, params=params)
    valid_sets = [Dataset(v, params=params, reference=train_set)
                  for v in cfg.valid_data]
    valid_names = [f"valid_{i}" for i in range(len(valid_sets))]
    resume = cfg.resume_from or None
    booster = train(params, train_set, num_boost_round=cfg.num_iterations,
                    valid_sets=valid_sets, valid_names=valid_names,
                    init_model=(cfg.input_model or None)
                    if not resume else None,
                    early_stopping_rounds=cfg.early_stopping_round or None,
                    verbose_eval=cfg.output_freq,
                    resume_from=resume)
    import jax
    if jax.process_index() == 0:    # every rank holds the identical model
        booster.save_model(cfg.output_model)
        log_info(f"finished training; model saved to {cfg.output_model}")
    _write_telemetry_summary(cfg)


def _write_telemetry_summary(cfg: Config) -> None:
    """After a traced train: every rank's summary merged over the host
    collective, written by rank 0 as ``<telemetry_output>.summary.json``
    (single-host: this rank's summary, same file name)."""
    if not cfg.telemetry_output:
        return
    from . import obs
    import jax
    merged = None
    if jax.process_count() > 1:
        from .io.distributed import jax_process_allgather
        merged = obs.merged_summary(jax_process_allgather)
        if jax.process_index() != 0:
            return
    path = cfg.telemetry_output + ".summary.json"
    obs.write_summary(path, merged)
    log_info(f"telemetry summary written to {path}")


def _load_predict_input(cfg: Config):
    from .io.loader import parse_file
    X, label, _w, _q, _names, _cat = parse_file(cfg.data, cfg)
    return X, label


def _run_predict(cfg: Config, params) -> None:
    from .basic import Booster
    if not cfg.input_model:
        raise ValueError("task=predict requires input_model=<file>")
    booster = Booster(params=dict(params), model_file=cfg.input_model)
    X, _ = _load_predict_input(cfg)
    if cfg.is_predict_leaf_index:
        out = booster.predict(X, pred_leaf=True,
                              num_iteration=cfg.num_iteration_predict)
    elif cfg.is_predict_contrib:
        out = booster.predict(X, pred_contrib=True,
                              num_iteration=cfg.num_iteration_predict)
    else:
        out = booster.predict(X, raw_score=cfg.is_predict_raw_score,
                              num_iteration=cfg.num_iteration_predict)
    out = np.asarray(out)
    if out.ndim == 1:
        out = out[:, None]
    from .utils.file_io import open_write
    with open_write(cfg.output_result) as _f:
        np.savetxt(_f, out, delimiter="\t", fmt="%.9g")
    log_info(f"finished prediction; results saved to {cfg.output_result}")


def _run_refit(cfg: Config, params) -> None:
    """task=refit (application.cpp:293-318 KRefitTree): re-estimate leaf
    outputs of an existing model on new data."""
    from .basic import Booster, Dataset
    if not cfg.input_model:
        raise ValueError("task=refit requires input_model=<file>")
    booster = Booster(params=dict(params), model_file=cfg.input_model)
    data = Dataset(cfg.data, params=dict(params))
    data.construct()
    booster._gbdt.refit_dataset(data._constructed)
    booster.save_model(cfg.output_model)
    log_info(f"finished refit; model saved to {cfg.output_model}")


def _run_convert(cfg: Config, params) -> None:
    """task=convert_model: if-else code generation
    (gbdt_model_text.cpp:51-233 ModelToIfElse).  Emits C++."""
    from .basic import Booster
    from .models.codegen import model_to_ifelse
    booster = Booster(params=dict(params), model_file=cfg.input_model)
    code = model_to_ifelse(booster._gbdt)
    out = cfg.convert_model
    from .utils.file_io import open_write
    with open_write(out) as f:
        f.write(code)
    log_info(f"model converted to if-else code at {out}")


def main() -> int:
    argv = sys.argv[1:]
    if not argv:
        print(__doc__)
        return 1
    return run(argv)


if __name__ == "__main__":
    sys.exit(main())
