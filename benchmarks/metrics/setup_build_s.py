"""Layer: entry points. The part of set-up spent building the model: the
total of the program's ``build/*`` sections (``build/init``:
``ComputationGraph.init`` / ``MultiLayerNetwork.init``;
``build/import_graph``: a TF GraphDef mapped to a ``SameDiff``) as the window
starts, so everything before it is set-up. A program without the sections
gives nothing to read."""

PREFIX = "build/"


def start(ctx):
    from deeplearning4j_tpu.common.profiler import OpProfiler

    found = [row["total_s"]
             for name, row in OpProfiler.get().get_statistics().items()
             if name.startswith(PREFIX)]
    ctx["setup_build_s"] = sum(found) if found else None


def read(ctx):
    return ctx["setup_build_s"]
