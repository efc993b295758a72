# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU, which
    has no native lowering, and nowhere else — an accelerator runs the
    compiled kernel or none (the platform is ``energymodel.platform``)."""
    from repro.core.energymodel import platform
    return platform() == "cpu"
