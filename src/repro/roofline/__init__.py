from repro.roofline.analysis import (
    HW_V5E,
    Hardware,
    RooflineReport,
    collective_bytes_from_hlo,
    model_flops,
    active_param_count,
    roofline_terms,
)
from repro.roofline.engine_costs import (
    PEAKS,
    achieved_vs_peak,
    detect_hardware,
    engine_kernel_report,
    hardware_info,
    kernel_probe,
)

__all__ = [
    "HW_V5E",
    "PEAKS",
    "Hardware",
    "RooflineReport",
    "achieved_vs_peak",
    "collective_bytes_from_hlo",
    "detect_hardware",
    "engine_kernel_report",
    "hardware_info",
    "kernel_probe",
    "model_flops",
    "active_param_count",
    "roofline_terms",
]
