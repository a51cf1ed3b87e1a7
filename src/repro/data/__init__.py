from repro.data.synthetic_ctr import (  # noqa: F401
    CTRDataConfig,
    auc,
    generate,
    to_dense_batch,
    train_val_test,
)
from repro.data.common_feature import (  # noqa: F401
    flops_per_eval,
    memory_bytes,
    pad_to_multiple,
    shard_sessions,
)
from repro.data.sparse import (  # noqa: F401
    FlatCTRBatch,
    SlicedPlan,
    SparseCTRBatch,
    TransposePlan,
    build_batch_plans,
    build_transpose_plan,
    generate_sparse,
    pad_theta,
    sparse_loss_and_grad,
    sparse_matmul,
    sparse_nll,
    sparse_predict,
    sparse_predict_flat,
)
from repro.data.tokens import TokenStream, host_sharded_stream  # noqa: F401
