"""Design-space exploration walkthrough — the paper's workflow as a tool.

Compile the SPD LBM core, sweep the full (n, m) lattice on the FPGA model
and the (block_h, m) lattice on the TPU model in batched NumPy, extract
the Pareto frontiers, execute the TPU frontiers through real Pallas
kernels — the hand-written ``lbm_stream`` for LBM *and* the generic
SPD→Pallas codegen path for the 2-D diffusion app — and plan LM meshes
with the same spatial/temporal trade-off:

    PYTHONPATH=src python examples/dse_explore.py --arch granite-34b

or, after ``pip install -e .``, simply ``repro-explore``. Use
``--no-execute`` to skip the kernel runs (interpreted on the CPU,
compiled on a TPU),
``--topk`` to execute more frontier points, ``--devices N`` to sweep the
device axis d (multi-chip sharding with halo exchange; off-TPU force
host devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
so d > 1 frontier points actually run), ``--strategy refine|halving``
with ``--budget N`` to autotune measured-in-the-loop under a hard
measurement budget (docs/pipeline.md §search), and ``--json PATH`` to
dump the results — including strategy/budget accounting — for
scripting. The implementation lives in :mod:`repro.cli` so the
installed console script and this checkout script stay one code path.
"""

from repro.cli import explore_main

if __name__ == "__main__":
    explore_main()
