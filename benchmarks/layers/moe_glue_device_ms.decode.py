"""Device ms a call of the prompt pass's expert path outside its
``moe_experts_prefill_*`` kernels: ``moe/route`` + ``moe/experts`` +
``moe/combine`` (the sort, the gathers, the size count, the gate, the way back
to the tokens). Prints the parts."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "moe_glue_device_ms.decode",
                       lambda name, row: row["phase"] == "prefill" and row["layer"] in scopes.MOE_GLUE_LAYERS
                       and scopes.EXPERT_KERNEL_NAME_HOLDS not in name.lower(),
                       parts=lambda name, row: row["layer"])
