"""Monitoring backends (reference ``deepspeed/monitor/``: MonitorMaster
fanning out write_events to TensorBoard / WandB / CSV / Comet writers)."""

from __future__ import annotations

import csv
import os
from typing import Any, List, Tuple

import jax

from ..utils.logging import logger

Event = Tuple[str, Any, int]  # (tag, value, step)


class Monitor:
    def __init__(self, config):
        self.enabled = config.enabled

    def write_events(self, event_list: List[Event]) -> None:
        raise NotImplementedError


class TensorBoardMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        self.summary_writer = None
        if self.enabled and jax.process_index() == 0:
            try:
                from torch.utils.tensorboard import SummaryWriter
                log_dir = os.path.join(config.output_path or "./runs", config.job_name)
                self.summary_writer = SummaryWriter(log_dir=log_dir)
            except Exception as e:
                logger.warning("tensorboard unavailable: %s", e)
                self.enabled = False

    def write_events(self, event_list: List[Event]) -> None:
        if self.summary_writer is None:
            return
        for tag, value, step in event_list:
            self.summary_writer.add_scalar(tag, float(value), int(step))
        self.summary_writer.flush()


class CSVMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        self.output_path = config.output_path or "./csv_monitor"
        self.job_name = config.job_name
        # tag -> (file handle, csv.writer): one open append handle per
        # tag for the life of the monitor (an open+close per EVENT was
        # the dominant cost of a steps_per_print flush), flushed once
        # per write_events batch
        self._files = {}

    def _writer(self, tag: str):
        entry = self._files.get(tag)
        if entry is None:
            fname = os.path.join(self.output_path, self.job_name,
                                 tag.replace("/", "_") + ".csv")
            os.makedirs(os.path.dirname(fname), exist_ok=True)
            new = not os.path.exists(fname) or os.path.getsize(fname) == 0
            f = open(fname, "a", newline="")
            w = csv.writer(f)
            if new:
                w.writerow(["step", tag])
            entry = self._files[tag] = (f, w)
        return entry

    def write_events(self, event_list: List[Event]) -> None:
        if not self.enabled or jax.process_index() != 0:
            return
        touched = []
        for tag, value, step in event_list:
            f, w = self._writer(tag)
            w.writerow([int(step), float(value)])
            touched.append(f)
        for f in touched:
            f.flush()

    def close(self) -> None:
        for f, _ in self._files.values():
            f.close()
        self._files.clear()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class WandbMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        self._wandb = None
        if self.enabled and jax.process_index() == 0:
            try:
                import wandb
                wandb.init(project=config.project or "deepspeed_tpu",
                           group=config.group or None, team=config.team or None)
                self._wandb = wandb
            except Exception as e:
                logger.warning("wandb unavailable: %s", e)
                self.enabled = False

    def write_events(self, event_list: List[Event]) -> None:
        if self._wandb is None:
            return
        for tag, value, step in event_list:
            self._wandb.log({tag: value}, step=int(step))


class CometMonitor(Monitor):
    """Comet experiment writer (reference monitor/comet.py CometMonitor);
    gated import — comet_ml is not in the image, so this degrades to
    disabled with a warning rather than failing."""

    def __init__(self, config):
        super().__init__(config)
        self._exp = None
        if self.enabled and jax.process_index() == 0:
            try:
                import comet_ml
                self._exp = comet_ml.Experiment(
                    project_name=config.project or "deepspeed_tpu",
                    workspace=config.team or None)
                if config.job_name:
                    self._exp.set_name(config.job_name)
            except Exception as e:
                logger.warning("comet_ml unavailable: %s", e)
                self.enabled = False

    @property
    def experiment(self):
        return self._exp

    def write_events(self, event_list: List[Event]) -> None:
        if self._exp is None:
            return
        for tag, value, step in event_list:
            self._exp.log_metric(tag, value, step=int(step))


class MonitorMaster(Monitor):
    """Fan-out master (reference monitor/monitor.py:30)."""

    def __init__(self, ds_config):
        self.monitors: List[Monitor] = []
        if ds_config.tensorboard.enabled:
            self.monitors.append(TensorBoardMonitor(ds_config.tensorboard))
        if ds_config.csv_monitor.enabled:
            self.monitors.append(CSVMonitor(ds_config.csv_monitor))
        if ds_config.wandb.enabled:
            self.monitors.append(WandbMonitor(ds_config.wandb))
        if ds_config.comet.enabled:
            self.monitors.append(CometMonitor(ds_config.comet))
        self.enabled = any(m.enabled for m in self.monitors)

    def write_events(self, event_list: List[Event]) -> None:
        for m in self.monitors:
            if m.enabled:
                m.write_events(event_list)

    def write_registry_snapshot(self, step: int) -> None:
        """Publish the telemetry registry's ``snapshot()`` through every
        enabled writer under ``Telemetry/<metric>`` tags — the SAME
        names (and values) the /metrics endpoint and the tests read, so
        monitor artifacts stop being a fifth metrics namespace.  Called
        by the engine at the ``steps_per_print`` cadence.  Metrics that
        have never recorded anything (zero counters, never-observed
        histograms, unbound/unset gauges) are skipped — a training-only
        process does not fan out ~40 all-zero serving series per flush."""
        if not self.enabled:
            return
        from ..telemetry import Counter, Gauge, Histogram, get_registry
        events: List[Event] = []
        for name, m in sorted(get_registry().all_metrics().items()):
            if isinstance(m, Histogram):
                if m.count == 0:
                    continue
                events += [(f"Telemetry/{name}_p50", m.percentile(50), step),
                           (f"Telemetry/{name}_p90", m.percentile(90), step),
                           (f"Telemetry/{name}_p99", m.percentile(99), step),
                           (f"Telemetry/{name}_count", m.count, step),
                           (f"Telemetry/{name}_mean", m.mean, step)]
            elif isinstance(m, Counter):
                if m.value:
                    events.append((f"Telemetry/{name}", m.value, step))
            elif isinstance(m, Gauge):
                if m.touched:
                    events.append((f"Telemetry/{name}", m.value, step))
        self.write_events(events)
