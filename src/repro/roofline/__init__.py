from repro.roofline.analysis import (PEAKS, analyze_compiled, peaks,  # noqa: F401
                                     collective_bytes_from_hlo,
                                     roofline_terms)
from repro.roofline.linear_bytes import (fusion_report,  # noqa: F401
                                         linear_pipeline_bytes, tp_sweep)
