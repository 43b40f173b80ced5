from .zoo import (AlexNet, Darknet19, FaceNetNN4Small2, GraniteHybrid,
                  InceptionResNetV1, JoyAILLMFlash, LeNet, Lfm2Moe, NASNet,
                  Phi4MiniFlash, ResNet50, SimpleCNN, SqueezeNet,
                  TextGenerationLSTM, TinyYOLO, TrinityMini, UNet, VGG16,
                  VGG19, Xception,
                  YOLO2, ZooModel, PretrainedType)
