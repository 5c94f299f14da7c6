# Pallas TPU kernels for the compute hot spots (validated in interpret mode
# on CPU against the ref.py oracles; compile to Mosaic on TPU backends):
#   flash_attention.py  — GQA/causal/window/softcap blocked online softmax
#                         with per-row lengths, block skipping and a
#                         custom-VJP backward (MLA's long training rows)
#   fused_policy_mlp.py — whole Table-6 policy trunk in one VMEM-resident call
#   mlstm_scan.py       — chunkwise mLSTM matrix-memory recurrence
from repro.kernels import ops, ref  # noqa: F401
