from realtime_fraud_detection_tpu.ops.attention import (  # noqa: F401
    attention_reference,
    flash_attention,
    flash_supported,
    merge_heads,
    narrowest_supported_len,
    split_heads,
)
from realtime_fraud_detection_tpu.ops.dequant_matmul import (  # noqa: F401
    dequant_matmul,
    dequant_matmul_reference,
    dequant_rows,
    dequant_rows_reference,
    matmul_supported,
    rows_supported,
)
from realtime_fraud_detection_tpu.ops.epilogue import (  # noqa: F401
    combine_matrix,
    epilogue_reference,
    epilogue_supported,
    fused_epilogue,
)
from realtime_fraud_detection_tpu.ops.grouped_matmul import (  # noqa: F401
    grouped_matmul,
    grouped_matmul_reference,
    grouped_matmul_supported,
)
