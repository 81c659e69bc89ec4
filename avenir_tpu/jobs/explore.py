"""Exploration jobs — mutual information, categorical correlation, and class
samplers (explore/MutualInformation.java, CramerCorrelation.java,
HeterogeneityReductionCorrelation.java, BaggingSampler.java,
UnderSamplingBalancer.java) on the in-process TPU engine.
"""

from __future__ import annotations

from typing import List

import jax
import numpy as np

from avenir_tpu.core.config import JobConfig
from avenir_tpu.jobs.base import Job, write_output
from avenir_tpu.models import correlation as corr
from avenir_tpu.models import mutual_info as mi
from avenir_tpu.models import samplers
from avenir_tpu.utils.metrics import Counters


def mi_output_lines(conf: JobConfig, result, names: List[str]) -> List[str]:
    """The MutualInformation job's output lines from a finished result —
    the ONE assembly used by both the standalone job and the SharedScan
    fused path (``pipeline/scan.py``), so the two can never drift."""
    delim = conf.field_delim
    lines: List[str] = []
    if conf.get_bool("output.mutual.info", True):
        lines.extend(result.to_lines(delim=delim))
    for algo in conf.get_list("mutual.info.score.algorithms", ["mim"]):
        kwargs = {}
        if algo == "mifs":
            kwargs["redundancy_factor"] = conf.get_float(
                "mutual.info.redundancy.factor", 1.0)
        ranked = mi.score_features(result, algo, **kwargs)
        lines.append(f"featureScore:{algo}")
        lines.extend(
            delim.join([names[f], f"{score:.6f}"]) for f, score in ranked)
    return lines


def correlation_plan(conf: JobConfig, schema, enc):
    """(src_idx, dst_idx, against_class, names) for a correlation job's
    attribute selection — shared by the standalone jobs and the SharedScan
    fused path.  Source/dest attribute lists arrive as schema ordinals
    (CramerCorrelation.java:95-100) and are mapped to binned indices; a
    dest list of exactly the class ordinal selects against-class mode."""
    binned_ords = [f.ordinal for f in enc.binned_fields]
    names = [schema.field_by_ordinal(o).name for o in binned_ords]
    ord_to_idx = {o: i for i, o in enumerate(binned_ords)}
    src = conf.get_int_list("source.attributes")
    dst = conf.get_int_list("dest.attributes")
    class_ord = schema.class_field.ordinal if schema.class_field else None
    against_class = dst is not None and class_ord is not None and dst == [class_ord]
    src_idx = [ord_to_idx[o] for o in src] if src else None
    dst_idx = (None if against_class or dst is None
               else [ord_to_idx[o] for o in dst])
    return src_idx, dst_idx, against_class, names


class MutualInformation(Job):
    """One-pass distributions + MI + feature-selection scores.

    Output sections mirror the reference reducer's cleanup
    (MutualInformation.java:462-471): all distributions, mutual-information
    values, then one ranked feature subset per algorithm in
    ``mutual.info.score.algorithms`` (mim/mifs/jmi/disr/mrmr;
    MutualInformationScore.java).
    """

    name = "MutualInformation"

    def execute(self, conf: JobConfig, input_path: str, output_path: str,
                counters: Counters) -> None:
        schema = self.load_schema(conf)
        mesh = self.auto_mesh(conf)
        ckpt = self.stream_checkpointer(conf)
        # multi-process execution: see BayesianDistribution.execute
        owner, acc, distributed = self.distributed_plan(conf, ckpt)
        enc, data, rows_fn = self.encoded_data_source(conf, input_path, counters,
                                                      mesh=mesh,
                                                      checkpointer=ckpt,
                                                      owner=owner)
        names = [schema.field_by_ordinal(f.ordinal).name
                 for f in enc.binned_fields]
        merged: dict = {}
        engine = mi.MutualInformation(mesh=mesh)
        if distributed:
            data = self.distributed_stream(data, acc, rows_fn, merged)
            result = self.distributed_fit(
                lambda d: engine.fit(d, feature_names=names,
                                     accumulator=acc),
                data, acc, merged)
            if result is None:             # zero-chunk non-writer process
                counters.set("Records", "Processed", merged["rows"])
                return
        else:
            result = engine.fit(data, feature_names=names, accumulator=acc)
        # which count route this run took (kernel / sharded / einsum) and
        # over how many chunks — the fused scan journals the same tag on
        # its `scan` span; the standalone job had no record of it
        counters.set("Records", f"CountPath.{engine.count_path}",
                     engine.chunks_seen)
        lines = mi_output_lines(conf, result, names)
        rows = merged["rows"] if distributed else rows_fn()
        if self.is_output_writer():
            write_output(output_path, lines)
        if ckpt:
            ckpt.finish()
        counters.set("Records", "Processed", rows)


class _CorrelationJob(Job):
    algorithm = "cramerIndex"

    def _algorithm(self, conf: JobConfig) -> str:
        return self.algorithm

    def execute(self, conf: JobConfig, input_path: str, output_path: str,
                counters: Counters) -> None:
        delim = conf.field_delim
        schema = self.load_schema(conf)
        mesh = self.auto_mesh(conf)
        ckpt = self.stream_checkpointer(conf)
        # multi-process execution: see BayesianDistribution.execute — the
        # reference ran this same Tool across N machines
        # (CramerCorrelation.java:83); contingency counts are exact
        # integers, so the end-of-stream merge is order-free
        owner, acc, distributed = self.distributed_plan(conf, ckpt)
        enc, data, rows_fn = self.encoded_data_source(conf, input_path, counters,
                                                      mesh=mesh,
                                                      checkpointer=ckpt,
                                                      owner=owner)
        src_idx, dst_idx, against_class, names = correlation_plan(conf, schema, enc)
        job = corr.CategoricalCorrelation(algorithm=self._algorithm(conf),
                                          mesh=mesh)
        fit = lambda d: job.fit(
            d,
            src=src_idx,
            dst=dst_idx,
            against_class=against_class,
            feature_names=names,
            accumulator=acc,
        )
        merged: dict = {}
        if distributed:
            data = self.distributed_stream(data, acc, rows_fn, merged)
            result = self.distributed_fit(fit, data, acc, merged)
        else:
            result = fit(data)
        rows = merged["rows"] if distributed else rows_fn()
        if result is not None and self.is_output_writer():
            write_output(output_path, result.to_lines(delim=delim))
        if ckpt:
            ckpt.finish()
        counters.set("Records", "Processed", rows)


class CramerCorrelation(_CorrelationJob):
    name = "CramerCorrelation"
    algorithm = "cramerIndex"


class HeterogeneityReductionCorrelation(_CorrelationJob):
    name = "HeterogeneityReductionCorrelation"

    def _algorithm(self, conf: JobConfig) -> str:
        # reference values: concentration | uncertainty
        # (HeterogeneityReductionCorrelation.java:70-84)
        algo = conf.get("heterogeneity.algorithm", "concentration")
        return {"concentration": "concentrationCoeff",
                "uncertainty": "uncertaintyCoeff"}.get(algo, algo)


class BaggingSampler(Job):
    """Bootstrap sample with replacement (BaggingSampler.java:100-122) —
    row-level resampling of the raw CSV, batch by batch."""

    name = "BaggingSampler"

    def execute(self, conf: JobConfig, input_path: str, output_path: str,
                counters: Counters) -> None:
        # pure row-level resampling: fields are never inspected, so read raw
        # lines (no CSV parse, no schema needed) and emit them verbatim
        from avenir_tpu.jobs.base import read_lines

        lines = read_lines(input_path)
        batch = conf.get_int("batch.size", 10_000)
        key = jax.random.PRNGKey(conf.get_int("seed", 0))
        out: List[str] = []
        for s in range(0, len(lines), batch):
            chunk = lines[s:s + batch]
            key, sub = jax.random.split(key)
            idx = np.asarray(samplers.bootstrap_indices(sub, len(chunk)))
            out.extend(chunk[i] for i in idx)
        write_output(output_path, out)
        counters.set("Records", "Processed", len(lines))
        counters.set("Records", "Emitted", len(out))


class UnderSamplingBalancer(Job):
    """Majority-class undersampler (UnderSamplingBalancer.java:92-164): keep
    minority rows, thin majority rows to p = minCount/classCount."""

    name = "UnderSamplingBalancer"

    def execute(self, conf: JobConfig, input_path: str, output_path: str,
                counters: Counters) -> None:
        import jax.numpy as jnp

        from avenir_tpu.jobs.base import read_lines

        # only the class column is inspected: read raw lines and slice the
        # class field per row — feature columns are never parsed, so data
        # the downstream jobs would reject (sentinels in numeric columns,
        # class values outside a declared cardinality) still samples fine,
        # exactly as the reference's mapper behaved
        schema = self.load_schema(conf)
        if schema.class_field is None:
            raise ValueError("undersampling requires a class attribute")
        class_ord = schema.class_field.ordinal
        delim = conf.field_delim_regex
        lines = read_lines(input_path)
        labels_raw = [ln.split(delim)[class_ord] for ln in lines]
        _values, inverse, cts = np.unique(
            np.asarray(labels_raw, dtype=object).astype(str),
            return_inverse=True, return_counts=True)
        key = jax.random.PRNGKey(conf.get_int("seed", 0))
        mask = np.asarray(samplers.undersample_mask(
            key, jnp.asarray(inverse.astype(np.int32)),
            jnp.asarray(cts.astype(np.float32))))
        out = [lines[i] for i in np.nonzero(mask)[0]]
        write_output(output_path, out)
        counters.set("Records", "Processed", len(lines))
        counters.set("Records", "Emitted", len(out))
