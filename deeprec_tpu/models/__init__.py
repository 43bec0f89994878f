from deeprec_tpu.models.wdl import WDL
from deeprec_tpu.models.dlrm import DLRM, DLRMDCN
from deeprec_tpu.models.deepfm import DeepFM
from deeprec_tpu.models.dcn import DCN, DCNv2
from deeprec_tpu.models.din import DIN
from deeprec_tpu.models.dien import DIEN
from deeprec_tpu.models.bst import BST
from deeprec_tpu.models.dssm import DSSM
from deeprec_tpu.models.masknet import MaskNet
from deeprec_tpu.models.hybrid_stack import HybridStackLM
from deeprec_tpu.models.window_stack import WindowStackLM
from deeprec_tpu.models.latent_stack import LatentStackLM
from deeprec_tpu.models.mamba_stack import MambaStackLM
from deeprec_tpu.models.conv_stack import ConvStackLM
from deeprec_tpu.models.multitask import DBMTL, ESMM, MMoE, PLE, SimpleMultiTask
from deeprec_tpu.models.registry import REGISTRY, build_model
