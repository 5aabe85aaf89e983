"""CLI dispatch: ``python -m variantcalling_tpu <tool> <args>`` (or ``vctpu <tool>``).

Mirrors the reference's ugvc CLI surface (ugvc/__main__.py:43-105): each tool
is a module exposing ``run(argv)`` with its own argparse parser; tools are
lazily imported so the CLI stays fast and optional heavy deps stay optional.
"""

from __future__ import annotations

import importlib
import sys

# tool name -> module path (module must expose run(argv))
TOOLS: dict[str, str] = {
    "knobs": "variantcalling_tpu.knobs",
    "obs": "variantcalling_tpu.obs.cli",
    "serve": "variantcalling_tpu.serve.cli",
    "merge-ranks": "variantcalling_tpu.parallel.rank_plan",
    "filter_variants_pipeline": "variantcalling_tpu.pipelines.filter_variants",
    "train_models_pipeline": "variantcalling_tpu.pipelines.train_models",
    "training_prep_pipeline": "variantcalling_tpu.pipelines.training_prep",
    "run_comparison_pipeline": "variantcalling_tpu.pipelines.run_comparison",
    "evaluate_concordance": "variantcalling_tpu.pipelines.evaluate_concordance",
    "coverage_analysis": "variantcalling_tpu.pipelines.coverage_analysis",
    "correct_systematic_errors": "variantcalling_tpu.pipelines.sec.correct_systematic_errors",
    "sec_training": "variantcalling_tpu.pipelines.sec.sec_training",
    "sec_validation": "variantcalling_tpu.pipelines.sec.sec_validation",
    "assess_sec_concordance": "variantcalling_tpu.pipelines.sec.assess_sec_concordance",
    "concat_methyldackel_csvs": "variantcalling_tpu.pipelines.methylation.concat_methyldackel_csvs",
    "process_mbias": "variantcalling_tpu.pipelines.methylation.process_mbias",
    "process_merge_context": "variantcalling_tpu.pipelines.methylation.process_merge_context",
    "process_merge_context_no_cp_g": "variantcalling_tpu.pipelines.methylation.process_merge_context_no_cp_g",
    "process_per_read": "variantcalling_tpu.pipelines.methylation.process_per_read",
    "cloud_sync": "variantcalling_tpu.pipelines.misc.cloud_sync",
    "sorter_to_h5": "variantcalling_tpu.pipelines.misc.sorter_to_h5",
    "sorter_stats_to_mean_coverage": "variantcalling_tpu.pipelines.misc.sorter_stats_to_mean_coverage",
    "collect_existing_metrics": "variantcalling_tpu.pipelines.misc.collect_existing_metrics",
    "convert_h5_to_json": "variantcalling_tpu.pipelines.misc.convert_h5_to_json",
    "annotate_contig": "variantcalling_tpu.pipelines.vcfbed.annotate_contig",
    "intersect_bed_regions": "variantcalling_tpu.pipelines.vcfbed.intersect_bed_regions",
    "find_runs_bed": "variantcalling_tpu.pipelines.misc.find_runs_bed",
    "index_vcf_file": "variantcalling_tpu.pipelines.misc.index_vcf_file",
    "remove_vcf_duplicates": "variantcalling_tpu.pipelines.misc.remove_vcf_duplicates",
    "remove_empty_files": "variantcalling_tpu.pipelines.misc.remove_empty_files",
    "correct_genotypes_by_imputation": "variantcalling_tpu.pipelines.correct_genotypes_by_imputation",
    "convert_haploid_regions": "variantcalling_tpu.pipelines.convert_haploid_regions",
    "compress_gvcf": "variantcalling_tpu.pipelines.compress_gvcf",
    "cleanup_gvcf_before_calling": "variantcalling_tpu.pipelines.cleanup_gvcf_before_calling",
    "gvcf_hcr": "variantcalling_tpu.pipelines.gvcf_hcr",
    "denovo_recalibrated_qualities": "variantcalling_tpu.pipelines.denovo_recalibrated_qualities",
    "quick_fingerprinting": "variantcalling_tpu.pipelines.quick_fingerprinting",
    "sv_stats_collect": "variantcalling_tpu.pipelines.sv_stats_collect",
    "run_no_gt_report": "variantcalling_tpu.pipelines.run_no_gt_report",
    "vcfeval_flavors": "variantcalling_tpu.pipelines.vcfeval_flavors",
    "create_var_report": "variantcalling_tpu.pipelines.create_var_report",
    "create_sv_report": "variantcalling_tpu.pipelines.create_sv_report",
    "create_qc_report": "variantcalling_tpu.pipelines.create_qc_report",
    "joint_calling_report": "variantcalling_tpu.pipelines.joint_calling_report",
    "substitution_error_rate_report": "variantcalling_tpu.pipelines.substitution_error_rate_report",
    "import_metrics": "variantcalling_tpu.pipelines.import_metrics",
    "cnv_calling": "variantcalling_tpu.pipelines.cnv_calling",
    "srsnv_training": "variantcalling_tpu.pipelines.srsnv.srsnv_training",
    "srsnv_inference": "variantcalling_tpu.pipelines.srsnv.srsnv_inference",
    "mrd_analysis": "variantcalling_tpu.pipelines.mrd_analysis",
    "ppmseq_qc": "variantcalling_tpu.pipelines.ppmseq_qc",
    "create_somatic_gt_file": "variantcalling_tpu.pipelines.create_somatic_gt_file",
    "run_somatic_comparison_and_graphs": "variantcalling_tpu.pipelines.run_somatic_comparison_and_graphs",
    "train_dan": "variantcalling_tpu.pipelines.train_dan",
    "report_wo_gt": "variantcalling_tpu.pipelines.report_wo_gt",
    "mrd_data_analysis": "variantcalling_tpu.pipelines.mrd_data_analysis",
    "detailed_var_report": "variantcalling_tpu.pipelines.detailed_var_report",
    "find_adapter_coords": "variantcalling_tpu.pipelines.find_adapter_coords",
    "add_ml_tags_bam": "variantcalling_tpu.pipelines.add_ml_tags_bam",
    "collect_hpol_table": "variantcalling_tpu.pipelines.collect_hpol_table",
    "calibrate_bridging_snvs": "variantcalling_tpu.pipelines.calibrate_bridging_snvs",
    "training_set_consistency_check": "variantcalling_tpu.pipelines.training_set_consistency_check",
    "train_lib_prep_recalibration_model": "variantcalling_tpu.pipelines.lpr.train_lib_prep_recalibration_model",
    "filter_vcf_with_lib_prep_recalibration_model": (
        "variantcalling_tpu.pipelines.lpr.filter_vcf_with_lib_prep_recalibration_model"
    ),
}

_LOGO = "variantcalling-tpu (vctpu) — TPU-native variant-calling post-processing"


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in {"-h", "--help"}:
        print(_LOGO)
        print("usage: python -m variantcalling_tpu <tool> [tool args]\n\ntools:")
        for name in sorted(TOOLS):
            print(f"  {name}")
        return 0
    tool = argv[0]
    if tool not in TOOLS:
        print(f"unknown tool: {tool!r}; run with --help for the tool list", file=sys.stderr)
        return 2
    # configuration errors (EngineError — e.g. a malformed VCTPU_* knob
    # parsed during tool import or startup) exit 2 with the message, not
    # a traceback: the knob-registry contract at the dispatch level
    from variantcalling_tpu.engine import EngineError

    try:
        module = importlib.import_module(TOOLS[tool])
    except ModuleNotFoundError as e:
        print(f"tool {tool!r} is not available yet: {e}", file=sys.stderr)
        return 3
    except EngineError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    # unknown VCTPU_* variables are almost always typos of real knobs —
    # warn (with a closest-match suggestion) before the tool runs, so
    # VCTPU_FOERST_STRATEGY=wide can no longer be silently ignored
    from variantcalling_tpu import knobs

    knobs.warn_unknown_env()
    try:
        # multi-host launch: VCTPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID in
        # the env turn any tool into one rank of a global mesh
        # (parallel/distributed). Gated on the env so plain runs keep the
        # lazy-import fast path.
        if knobs.get_str("VCTPU_COORDINATOR") or knobs.get_bool("VCTPU_AUTO_DISTRIBUTED"):
            from variantcalling_tpu.parallel.distributed import init_from_env

            init_from_env()
        # per-file CLI invocations must not re-pay jit compiles: persist XLA
        # executables across processes (JAX_COMPILATION_CACHE_DIR, else the
        # fixed in-checkout directory — utils/compile_cache.py)
        from variantcalling_tpu.utils.compile_cache import enable_persistent_cache

        enable_persistent_cache()
        result = module.run(argv[1:])
    except EngineError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    # tools may return rich results (e.g. vcfeval_flavors' rows); only
    # int-like returns are exit codes
    return result if isinstance(result, int) else 0


if __name__ == "__main__":
    sys.exit(main())
