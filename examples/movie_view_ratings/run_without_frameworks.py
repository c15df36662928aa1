"""DP movie-view statistics over the REAL Netflix file format, no framework.

Counterpart of the reference's
examples/movie_view_ratings/run_without_frameworks.py: parse the
movie-ratings file, compute per-movie DP COUNT / SUM / PRIVACY_ID_COUNT
(plus PERCENTILEs under naive accounting), print the Explain Computation
report, write results to a file.

TPU-first difference: by default the aggregation runs on the fused columnar
device backend (pipelinedp_tpu.TPUBackend) — one jit-compiled XLA program —
on the accelerator JAX finds (JAX_PLATFORMS=cpu runs it CPU-only), and
file parsing is vectorized (pipelinedp_tpu.textfile). With --streaming
the engine is handed the file itself
(pdp.ChunkSource.from_netflix_file): its byte ranges are read and parsed
on the encode workers while earlier ranges are encoded and uploaded.

Usage:
    # With the real dataset:
    python run_without_frameworks.py --input_file=netflix.txt \\
        --output_file=out.txt
    # Or self-contained (generates a synthetic file in the same format):
    python run_without_frameworks.py --generate_rows 50000 \\
        --output_file=out.txt
    # Reference-style local Python backend / PLD accounting:
    python run_without_frameworks.py ... --local --pld_accounting
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import pipelinedp_tpu as pdp
from examples.movie_view_ratings import netflix_format


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--input_file", default=None,
                        help="movie view data in the Netflix file format")
    parser.add_argument("--output_file", default=None)
    parser.add_argument("--generate_rows", type=int, default=0,
                        help="generate a synthetic input file with this many "
                        "rows instead of reading --input_file")
    parser.add_argument("--pld_accounting", action="store_true",
                        help="PLD accounting instead of naive composition")
    parser.add_argument("--local", action="store_true",
                        help="pure-Python local backend instead of the fused "
                        "device backend")
    parser.add_argument("--streaming", action="store_true",
                        help="hand the engine the file: its byte ranges "
                        "are parsed and encoded on the encode workers "
                        "while earlier ones upload "
                        "(pdp.ChunkSource.from_netflix_file) — device "
                        "backend only")
    parser.add_argument("--epsilon", type=float, default=1.0)
    parser.add_argument("--delta", type=float, default=1e-6)
    args = parser.parse_args()

    input_file = args.input_file
    if args.generate_rows:
        input_file = os.path.join(tempfile.mkdtemp(), "movie_views.txt")
        netflix_format.generate_file(input_file, args.generate_rows)
        print(f"generated {args.generate_rows} rows -> {input_file}")
    if not input_file:
        parser.error("provide --input_file or --generate_rows")

    public_partitions = list(range(1, 100))
    if args.streaming:
        if args.local:
            parser.error("--streaming requires the device backend")
        movie_views = pdp.ChunkSource.from_netflix_file(input_file)
        print(f"streaming {input_file} in {len(movie_views.chunks)} "
              f"byte ranges")
    else:
        movie_views = netflix_format.parse_file(input_file)
        print(f"parsed {len(movie_views)} movie views")

    backend = pdp.LocalBackend() if args.local else pdp.TPUBackend()
    if args.pld_accounting:
        budget_accountant = pdp.PLDBudgetAccountant(
            total_epsilon=args.epsilon, total_delta=args.delta)
    else:
        budget_accountant = pdp.NaiveBudgetAccountant(
            total_epsilon=args.epsilon, total_delta=args.delta)
    engine = pdp.DPEngine(budget_accountant, backend)

    metrics = [
        pdp.Metrics.COUNT, pdp.Metrics.SUM, pdp.Metrics.PRIVACY_ID_COUNT
    ]
    if not args.pld_accounting:
        # PLD accounting does not support PERCENTILE (reference parity).
        metrics += [pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90)]
    params = pdp.AggregateParams(
        metrics=metrics,
        noise_kind=pdp.NoiseKind.GAUSSIAN,
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        min_value=1,
        max_value=5)
    data_extractors = pdp.DataExtractors(
        partition_extractor=lambda mv: mv.movie_id,
        privacy_id_extractor=lambda mv: mv.user_id,
        value_extractor=lambda mv: mv.rating)

    explain_computation_report = pdp.ExplainComputationReport()
    dp_result = engine.aggregate(
        movie_views,
        params,
        data_extractors,
        public_partitions=public_partitions,
        out_explain_computation_report=explain_computation_report)
    budget_accountant.compute_budgets()

    print(explain_computation_report.text())
    dp_result = list(dp_result)
    print(f"computed DP metrics for {len(dp_result)} movies; sample:")
    for pk, row in sorted(dp_result)[:3]:
        print(f"  movie {pk}: {row}")
    if args.output_file:
        netflix_format.write_to_file(dp_result, args.output_file)
        print(f"wrote {args.output_file}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
