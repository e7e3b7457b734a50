"""Pallas TPU kernels for the optimizer hot-spots the paper exercises.

Each kernel package has:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jitted wrapper with use_pallas/interpret dispatch
  ref.py    — pure-jnp oracle the tests assert against

ns_ortho      : blocked matmul + fused NS-quintic epilogue (Muon, MXU-bound)
sophia_update : fused momentum/clip/precondition pass (memory-bound)
soap_rotate   : two-sided eigenbasis rotation + fused rotated Adam
qblock        : fused blockwise int8 quantization (wire codec, memory-bound)
fused_agg     : fused dequantize-accumulate server flush (memory-bound)
householder_qr: QR panels factorized in VMEM inside a blocked Householder
                QR (SOAP's eigenbasis refresh); ``ops.qr_q`` routes by
                shape, ``ref`` is XLA's QR
"""
