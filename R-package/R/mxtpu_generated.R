# GENERATED by tools/gen_r_ops.py -- do not edit by hand.
# Reference capability: R-package/R/mxnet_generated.R (autogen
# wrappers for every registered operator + NDArray function).
# Regenerate: python tools/gen_r_ops.py ; CI asserts exactness.

# ------------------------- symbol operators (mx.symbol.<Op>)
# Activation: Elementwise activations (reference: activation-inl.h + mshadow_op.h)
mx.symbol.Activation <- function(...) mx.symbol.op("Activation", ...)
# BatchNorm: Batch normalization with running-stat aux state (reference:
mx.symbol.BatchNorm <- function(...) mx.symbol.op("BatchNorm", ...)
# BlockDiffusionAttention: Grouped-query attention under the mask of block-diffusion training
mx.symbol.BlockDiffusionAttention <- function(...) mx.symbol.op("BlockDiffusionAttention", ...)
# BlockGrad: Identity forward, zero gradient (reference: block_grad-inl.h) —
mx.symbol.BlockGrad <- function(...) mx.symbol.op("BlockGrad", ...)
# Concat: Concatenate along ``dim`` (reference: concat-inl.h, default channel 
mx.symbol.Concat <- function(...) mx.symbol.op("Concat", ...)
# Convolution: 2-D convolution (reference: convolution-inl.h). Weights are OIHW in
mx.symbol.Convolution <- function(...) mx.symbol.op("Convolution", ...)
# Deconvolution: Transposed convolution (reference: deconvolution-inl.h), implemented
mx.symbol.Deconvolution <- function(...) mx.symbol.op("Deconvolution", ...)
# Dropout: Inverted dropout (reference: dropout-inl.h — scales by 1/keep at tra
mx.symbol.Dropout <- function(...) mx.symbol.op("Dropout", ...)
# ElementWiseSum: Sum of N inputs (reference: elementwise_sum-inl.h; also the node typ
mx.symbol.ElementWiseSum <- function(...) mx.symbol.op("ElementWiseSum", ...)
# Embedding: Token embedding lookup (extension beyond v0.5; required by the LSTM/
mx.symbol.Embedding <- function(...) mx.symbol.op("Embedding", ...)
# Flatten: Collapse all dims after the first (reference: reshape-inl.h Flatten)
mx.symbol.Flatten <- function(...) mx.symbol.op("Flatten", ...)
# FullyConnected: Affine layer: Y = X·Wᵀ + b (reference: fully_connected-inl.h:53-118)
mx.symbol.FullyConnected <- function(...) mx.symbol.op("FullyConnected", ...)
# LRN: Local response normalization across channels (reference: lrn-inl.h):
mx.symbol.LRN <- function(...) mx.symbol.op("LRN", ...)
# LatentAttention: Causal multi-head latent attention (DeepSeek-V3, arXiv:2412.19437,
mx.symbol.LatentAttention <- function(...) mx.symbol.op("LatentAttention", ...)
# LeakyReLU: Leaky/parametric/randomized rectifiers (reference: leaky_relu-inl.h)
mx.symbol.LeakyReLU <- function(...) mx.symbol.op("LeakyReLU", ...)
# LinearRegressionOutput: Identity forward, (pred - label) gradient (reference:
mx.symbol.LinearRegressionOutput <- function(...) mx.symbol.op("LinearRegressionOutput", ...)
# LogisticRegressionOutput: Sigmoid forward, (pred - label) gradient (reference:
mx.symbol.LogisticRegressionOutput <- function(...) mx.symbol.op("LogisticRegressionOutput", ...)
# MAERegressionOutput: Identity forward, sign(pred - label) gradient (L1 regression head;
mx.symbol.MAERegressionOutput <- function(...) mx.symbol.op("MAERegressionOutput", ...)
# Mamba2Mixer: The mixer of a Mamba-2 layer on projected rows, rows = batch *
mx.symbol.Mamba2Mixer <- function(...) mx.symbol.op("Mamba2Mixer", ...)
# MaskedDiffusionOutput: Loss head of masked (absorbing-state) diffusion within blocks, the
mx.symbol.MaskedDiffusionOutput <- function(...) mx.symbol.op("MaskedDiffusionOutput", ...)
# MixtureOfExperts: Sparse gated feed-forward layer, one rank's share of its experts.
mx.symbol.MixtureOfExperts <- function(...) mx.symbol.op("MixtureOfExperts", ...)
# MixtureOfExpertsSquaredReLU: ``MixtureOfExperts`` with ungated experts (Nemotron-H's
mx.symbol.MixtureOfExpertsSquaredReLU <- function(...) mx.symbol.op("MixtureOfExpertsSquaredReLU", ...)
# Pooling: Max/avg/sum pooling, NCHW or NHWC per ``layout`` (reference:
mx.symbol.Pooling <- function(...) mx.symbol.op("Pooling", ...)
# RMSNorm: Root-mean-square normalisation over the last axis with a learnable
mx.symbol.RMSNorm <- function(...) mx.symbol.op("RMSNorm", ...)
# RematBoundary: Identity. Marks the end of a recomputation segment: the executor
mx.symbol.RematBoundary <- function(...) mx.symbol.op("RematBoundary", ...)
# Reshape: Reshape to ``target_shape`` (reference: reshape-inl.h; first dim 0 k
mx.symbol.Reshape <- function(...) mx.symbol.op("Reshape", ...)
# RotaryAttention: Causal grouped-query attention on projected rows.
mx.symbol.RotaryAttention <- function(...) mx.symbol.op("RotaryAttention", ...)
# SliceChannel: Split along axis 1 into ``num_outputs`` equal parts (reference:
mx.symbol.SliceChannel <- function(...) mx.symbol.op("SliceChannel", ...)
# SoftmaxOutput: Softmax forward + cross-entropy gradient injection (reference:
mx.symbol.SoftmaxOutput <- function(...) mx.symbol.op("SoftmaxOutput", ...)
# Transpose: Transpose (extension beyond v0.5, needed by attention models).
mx.symbol.Transpose <- function(...) mx.symbol.op("Transpose", ...)
mx.symbol.abs <- function(...) mx.symbol.op("abs", ...)
mx.symbol.exp <- function(...) mx.symbol.op("exp", ...)
mx.symbol.log <- function(...) mx.symbol.op("log", ...)
# norm: L2 norm reduction to a length-1 vector (reference: unary_function-in
mx.symbol.norm <- function(...) mx.symbol.op("norm", ...)
mx.symbol.sqrt <- function(...) mx.symbol.op("sqrt", ...)
mx.symbol.square <- function(...) mx.symbol.op("square", ...)

# ------------------- ndarray functions (mx.nd.<func>)
mx.nd.div <- function(a1, a2, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("_div", c(a1, a2), numeric(0), out)
  out
}
mx.nd.div.scalar <- function(a1, s1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("_div_scalar", c(a1), c(s1), out)
  out
}
mx.nd.minus.scalar <- function(a1, s1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("_minus_scalar", c(a1), c(s1), out)
  out
}
mx.nd.mul <- function(a1, a2, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("_mul", c(a1, a2), numeric(0), out)
  out
}
mx.nd.onehot.encode <- function(a1, a2, out) {
  .mxr.func("_onehot_encode", c(a1, a2), numeric(0), out)
  out
}
mx.nd.plus.scalar <- function(a1, s1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("_plus_scalar", c(a1), c(s1), out)
  out
}
mx.nd.random.gaussian <- function(s1, s2, out) {
  .mxr.func("_random_gaussian", integer(0), c(s1, s2), out)
  out
}
mx.nd.random.uniform <- function(s1, s2, out) {
  .mxr.func("_random_uniform", integer(0), c(s1, s2), out)
  out
}
mx.nd.rdiv.scalar <- function(a1, s1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("_rdiv_scalar", c(a1), c(s1), out)
  out
}
mx.nd.rminus.scalar <- function(a1, s1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("_rminus_scalar", c(a1), c(s1), out)
  out
}
mx.nd.set.value <- function(s1, out) {
  .mxr.func("_set_value", integer(0), c(s1), out)
  out
}
mx.nd.choose.element.0index <- function(a1, a2, out) {
  .mxr.func("choose_element_0index", c(a1, a2), numeric(0), out)
  out
}
mx.nd.clip <- function(a1, s1, s2, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("clip", c(a1), c(s1, s2), out)
  out
}
mx.nd.exp <- function(a1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("exp", c(a1), numeric(0), out)
  out
}
mx.nd.log <- function(a1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("log", c(a1), numeric(0), out)
  out
}
mx.nd.sqrt <- function(a1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("sqrt", c(a1), numeric(0), out)
  out
}
mx.nd.square <- function(a1, out = NULL) {
  if (is.null(out)) out <- .mxr.nd.alloc(mx.nd.shape(a1))
  .mxr.func("square", c(a1), numeric(0), out)
  out
}
